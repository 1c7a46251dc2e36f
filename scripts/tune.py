"""Batch-tune CNN workload scene sets and write the schedule-cache artifact.

Usage (off a TPU the kernels run in the Pallas interpreter):

    PYTHONPATH=src python scripts/tune.py --nets vgg --batch 8 --limit 2

On a TPU the kernels compile for the chip; drop the proxy caps:

    PYTHONPATH=src python scripts/tune.py --nets all --batch 128 \
        --measure-batch 0 --measure-max-ch 0 --measure-max-hw 0

Each scene is tuned through ``repro.tune.autotune_scene`` (analytic top-k
pruning -> wall-clock measurement through the real kernel dispatch) and the
winners land in the JSON cache (``--cache`` / $REPRO_TUNE_CACHE /
~/.cache/repro/tune_cache.json), where ``make_plan(scene, policy="tuned")``
resolves them.  Measured-vs-predicted error is reported per scene and
summarized — the audit trail for the analytic roofline model.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core.mapping import select_schedule           # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.cnn import cnn_scenes                  # noqa: E402
from repro.tune import ScheduleCache, autotune_scene     # noqa: E402
from repro.tune.autotune import error_summary            # noqa: E402
from repro.tune.cache import default_backend             # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--nets", default="vgg",
                    help='comma list of CNNs (see models/cnn.py) or "all"')
    ap.add_argument("--batch", type=int, default=8,
                    help="workload batch size for the scene set")
    ap.add_argument("--limit", type=int, default=0,
                    help="max scenes per net (0 = all)")
    ap.add_argument("--cache", default=None,
                    help="cache artifact path (default: env/home resolution)")
    ap.add_argument("--top-k", type=int, default=4,
                    help="measured candidates after analytic pruning")
    ap.add_argument("--iters", type=int, default=2)
    ap.add_argument("--warmup", type=int, default=1)
    ap.add_argument("--timeout-s", type=float, default=120.0)
    ap.add_argument("--measure-batch", type=int, default=2,
                    help="proxy cap on B for wall-clock (0 = exact)")
    ap.add_argument("--measure-max-ch", type=int, default=16,
                    help="proxy cap on IC/OC (0 = exact)")
    ap.add_argument("--measure-max-hw", type=int, default=8,
                    help="proxy cap on inH/inW (0 = exact)")
    ap.add_argument("--force", action="store_true",
                    help="re-measure scenes already in the cache")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    enable_compile_cache()
    all_scenes = cnn_scenes(args.batch)
    nets = list(all_scenes) if args.nets == "all" else args.nets.split(",")
    unknown = [n for n in nets if n not in all_scenes]
    if unknown:
        print(f"error: unknown net(s) {unknown}; known: {list(all_scenes)}",
              file=sys.stderr)
        return 2
    cache = ScheduleCache(args.cache)
    cap = lambda v: v if v > 0 else None

    errors, disagreements, tuned_total = [], 0, 0
    print(f"# cache: {cache.path} (backend={default_backend()})")
    print("scene,analytic,tuned,measured_us,analytic_measured_us,"
          "pred_err,n_cand")
    for net in nets:
        scenes = all_scenes[net]
        if args.limit:
            scenes = scenes[:args.limit]
        for i, sc in enumerate(scenes):
            t = autotune_scene(
                sc, cache=cache, top_k=args.top_k, iters=args.iters,
                warmup=args.warmup,
                timeout_s=args.timeout_s,
                measure_batch=cap(args.measure_batch),
                measure_max_ch=cap(args.measure_max_ch),
                measure_max_hw=cap(args.measure_max_hw),
                force=args.force)
            tuned_total += 1
            errors.append(t.prediction_error)
            disagreements += 0 if t.agrees_with_analytic else 1
            a = select_schedule(sc)
            tc = t.choice
            print(f"{net}_L{i},{a.schedule}({a.bm}/{a.bn}/{a.bk}),"
                  f"{tc.schedule}({tc.bm}/{tc.bn}/{tc.bk}),"
                  f"{t.measured_us:.1f},{t.analytic_measured_us:.1f},"
                  f"{t.prediction_error:.3f},{t.n_candidates}")
    path = cache.save()
    print(f"# wrote {len(cache)} entries -> {path}")
    if errors:
        # error_summary excludes non-finite rows (all-timed-out tunes score
        # prediction_error=inf) from mean/max and counts them instead
        es = error_summary(errors)
        print(f"# prediction error: mean={es['mean']:.3f} "
              f"max={es['max']:.3f} over {es['n_finite']}/{es['n']} scenes"
              + (f" ({es['n_nonfinite']} unmeasurable, excluded)"
                 if es["n_nonfinite"] else "")
              + f"; analytic disagreed on {disagreements}/{tuned_total} "
              f"scenes")
        print(f"# next: fit the cost model from these records -> "
              f"scripts/calibrate.py --cache {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
