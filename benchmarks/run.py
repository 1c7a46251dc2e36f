"""Benchmark harness: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV (plus roofline summaries if the
dry-run sweep results are present)."""
import argparse
import json
import os

from benchmarks import (batch, calibration, channels, cnns, filters,
                        granularity, padstride, plans, serving, sharding,
                        training, tuned)
from benchmarks.common import emit, parse_derived
from repro.launch.compile_cache import enable_compile_cache


def roofline_rows():
    out = []
    rdir = os.path.join(os.path.dirname(__file__), "..", "results")
    if not os.path.isdir(rdir):
        return out
    for fn in sorted(os.listdir(rdir)):
        if fn.startswith("roofline_") and fn.endswith(".json"):
            with open(os.path.join(rdir, fn)) as f:
                d = json.load(f)
            if d.get("status") != "ok":
                continue
            t = d["terms_s"]
            bound = max(t.values())
            out.append((f"roofline_{d['arch']}_{d['shape']}", bound * 1e6,
                        f"dominant={d['dominant']};"
                        f"frac={d.get('roofline_fraction', 0):.3f}"))
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: channels,batch,filters,"
                         "padstride,cnns,granularity,roofline,tuned,"
                         "calibration,plans,serving,serving_slo,sharding,"
                         "training")
    ap.add_argument("--plan", action="store_true",
                    help="also report plan-amortized dispatch overhead "
                         "(plan-once execute vs legacy per-call resolution)")
    ap.add_argument("--json", action="store_true",
                    help="emit one machine-readable JSON document instead "
                         "of CSV (CI and dashboards consume this)")
    args = ap.parse_args()
    enable_compile_cache()
    mods = {"channels": channels.rows, "batch": batch.rows,
            "filters": filters.rows, "padstride": padstride.rows,
            "cnns": cnns.rows, "granularity": granularity.rows,
            "roofline": roofline_rows, "tuned": tuned.rows,
            "calibration": calibration.rows, "plans": plans.rows,
            "serving": serving.rows, "serving_slo": serving.slo_rows,
            "sharding": sharding.rows, "training": training.rows}
    # the plans/serving/sharding/training tables are opt-in (they JIT-warm
    # whole plan ladders, need a forced multi-device host, compile train
    # steps, or pace live traffic for seconds): --plan appends plans,
    # --only isolates the rest
    only = args.only.split(",") if args.only else [
        m for m in mods if m not in ("plans", "serving", "serving_slo",
                                     "sharding", "training")]
    if args.plan and "plans" not in only:
        only.append("plans")
    if args.json:
        results = [{"table": name, "name": rname, "us_per_call": us,
                    "derived": str(derived),
                    "derived_fields": parse_derived(derived)}
                   for name in only
                   for rname, us, derived in mods[name]()]
        print(json.dumps({"kind": "repro-bench", "schema": 1,
                          "results": results}, indent=1))
        return
    print("name,us_per_call,derived")
    for name in only:
        emit(mods[name]())


if __name__ == "__main__":
    main()
