#!/usr/bin/env python3
"""Find an open-loop cell's knee on the chip, in one process:

    python3 bench/sweep.py --workload <cell> --rates 1,2,3 --seconds <s>

runs the cell's loop once per offered rate (the cell's traffic with
``rate_per_s`` replaced) and prints, per rate, the latency median and 95th
percentile, the median latency of the first and of the last third of the
requests, and the requests not answered when the window closed.  The knee
is the highest rate at which the backlog does not grow over the window:
the last third waits no longer than the first.  The cell's traffic file
then fixes a rate below it; the benchmark never searches for one.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness  # noqa: E402
from bench import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    type=lambda s: [float(r) for r in s.split(",")])
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu" and not args.cpu_rehearsal:
        print("sweep: JAX found no TPU", file=sys.stderr)
        return 2
    if not args.cpu_rehearsal:
        bench_run.enable_compile_cache()
    bench = harness.Bench(bench_run.ROOT)
    for rate in args.rates:
        _, ctx = bench_run.context(bench, args.workload, args.seed,
                                   args.seconds,
                                   rehearsal=args.cpu_rehearsal)
        ctx.traffic = dict(ctx.traffic, rate_per_s=rate)
        rec = ctx.loop.run(ctx)
        lat = rec["latencies_s"]
        third = max(1, len(lat) // 3)
        due = ctx.loop.arrival_offsets(rate, args.seconds, args.seed)
        done = [d + l for d, l in zip(due, lat)]
        print(json.dumps({
            "rate_per_s": rate, "requests": len(lat),
            "failed": rec["failed"],
            "p50_ms": 1e3 * harness.percentile(lat, 0.5),
            "p95_ms": 1e3 * harness.percentile(lat, 0.95),
            "first_third_p50_ms": 1e3 * statistics.median(lat[:third]),
            "last_third_p50_ms": 1e3 * statistics.median(lat[-third:]),
            "unanswered_at_close": int(sum(t > args.seconds for t in done)),
            "buckets": rec["buckets"], "dispatches": rec["dispatches"],
            "window_compiles": rec["window_compiles"],
            "correct": harness.judge(rec["checks"]),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
