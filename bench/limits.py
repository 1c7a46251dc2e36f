#!/usr/bin/env python3
"""The readings a cell's correctness limit is set from, on the chip, in one
process (set-up is paid once):

    python3 bench/limits.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 1,2,3 --seconds <s>

For each ``--seeds`` seed, one run of the cell as ``run.py`` makes it (a
window of ``--seconds``, the timed path's outputs compared with the
reference at float32); for each ``--control-seeds`` seed, the same run with
the control planted under ``ConvPlan.execute`` (``control.py``: the
reference at the step below float32, three bf16 passes), judged by the
same ``correct``.  Prints one JSON line per run (its side, seed, readings
and ``correct``) and, last, per number compared the largest program
reading (the lower end of the limit) and the smallest control reading (the
upper end), and whether every control run came out not correct.  The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness  # noqa: E402
from bench import run as bench_run  # noqa: E402


def seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from bench import control as bench_control

    if jax.devices()[0].platform != "tpu" and not args.cpu_rehearsal:
        print("limits: JAX found no TPU", file=sys.stderr)
        return 2
    if not args.cpu_rehearsal:
        bench_run.enable_compile_cache()
    bench = harness.Bench(bench_run.ROOT)
    program, control, control_failed = {}, {}, True

    def one(side, seed):
        line = bench_run.run_cell(bench, args.workload, seed, args.seconds,
                                  False, rehearsal=args.cpu_rehearsal)
        readings = {k: c["value"] for k, c in line["checks"].items()}
        print(json.dumps({"side": side, "seed": seed,
                          "correct": line["correct"],
                          "readings": readings}), flush=True)
        return line["correct"], readings

    for seed in args.seeds:
        for k, v in one("program", seed)[1].items():
            program[k] = max(program.get(k, v), v)
    cfg = bench.config(bench.workload(args.workload)["config"])
    for seed in args.control_seeds:
        with bench_control.planted(bench.reference(cfg["reference"])):
            correct, readings = one("control", seed)
        control_failed &= not correct
        for k, v in readings.items():
            control[k] = min(control.get(k, v), v)
    print(json.dumps({"workload": args.workload,
                      "program_max": program, "control_min": control,
                      "control_judged_not_correct": control_failed}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
