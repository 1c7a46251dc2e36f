"""Fit the calibrated cost model from the tuned schedule cache.

Usage (after a ``scripts/tune.py`` run populated the cache):

    PYTHONPATH=src python scripts/calibrate.py \
        --cache ~/.cache/repro/tune_cache.json \
        --out   ~/.cache/repro/calibration.json

Reads every tuned record, re-derives the roofline terms of the measured
execution, fits per-scene-class correction factors (effective MXU rate,
effective HBM bandwidth, per-grid-step overhead — ``repro.tune.calibrate``),
prints the per-class error report (median |predicted-measured|/measured
before -> after), and writes the versioned calibration artifact that
``make_plan(scene)`` and ``policy="tuned"`` cache misses pick up
automatically (path resolution: --out / $REPRO_CALIBRATION /
~/.cache/repro/calibration.json).

Re-fit whenever the cache gains meaningfully new scenes or a new backend
(CPU-interpret fits do not transfer to TPU — use --backend to keep them
apart).
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.tune import cache as cache_mod                # noqa: E402
from repro.tune import calibrate as calibrate_mod        # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cache", default=None,
                    help="tune cache artifact to fit from "
                         "(default: env/home resolution)")
    ap.add_argument("--out", default=None,
                    help="calibration artifact path (default: "
                         "$REPRO_CALIBRATION / ~/.cache/repro/"
                         "calibration.json)")
    ap.add_argument("--backend", default=None,
                    help='only fit records from this backend tag, e.g. '
                         '"cpu+interpret" or "tpu" (default: all)')
    ap.add_argument("--dry-run", action="store_true",
                    help="fit and report, but do not write the artifact")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    cache = cache_mod.ScheduleCache(args.cache)
    if len(cache) == 0:
        print(f"error: no tuned records in {cache.path}; run "
              f"scripts/tune.py first", file=sys.stderr)
        return 2
    report = calibrate_mod.fit_calibration(cache, backend=args.backend)
    if report.n_records == 0:
        print(f"error: {len(cache)} cache entries but none usable for "
              f"calibration (version/backend mismatch or unmeasurable "
              f"records; skipped {report.n_skipped})", file=sys.stderr)
        return 2

    print(f"# fit from {cache.path}: {report.n_records} records "
          f"({report.n_skipped} skipped"
          + (f", backend={args.backend}" if args.backend else "") + ")")
    print("class,n,method,compute_scale,bw_scale,overhead_ns,"
          "median_err_before,median_err_after")
    for f in report.classes:
        print(f"{f.cls},{f.n_samples},{f.method},{f.compute_scale:.4f},"
              f"{f.bw_scale:.4f},{f.overhead_s * 1e9:.2f},"
              f"{f.median_err_before:.3f},{f.median_err_after:.3f}")
    print(f"# overall median |pred-meas|/meas: "
          f"{report.median_err_before:.3f} -> {report.median_err_after:.3f}")

    if args.dry_run:
        print("# dry run: artifact not written")
        return 0
    path = calibrate_mod.save_calibration(report, args.out)
    print(f"# wrote calibration -> {path}")
    # Re-check the round trip: the artifact must reproduce the fit exactly.
    loaded = calibrate_mod.load_calibration(path)
    if loaded.corrections != report.cost_model().corrections:
        print("error: artifact round-trip mismatch", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
