#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell (``workloads`` in ``BENCHMARK.json``)
names a configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); the mix names the loop
(``bench/loops/<loop>.py``) that drives it.  Weights and inputs come
from ``--seed``.

Set-up (making weights and inputs on the device, building plans, compiling
or loading every shape the cell uses) runs first and is ``setup_s``; then
the loop measures for ``--seconds``.  Nothing compiles inside that window.
After it, the outputs the window produced are compared with the plain
reference of the configuration.

Output:

* standard error: progress, then as its last lines one ``check`` line per
  number compared, ``check <name> <value> limit <limit> ok|FAIL``;
* standard output, last line: one JSON object with ``correct`` (every
  number compared within its limit), ``attempted`` and ``failed`` (passes,
  or requests), ``metrics`` (``{name: {"value", "unit"}}``: the cell's
  ``end_to_end`` metrics with ``--trace 0``, its ``per_layer`` metrics with
  ``--trace 1``), ``device`` (``platform``, ``kind``, ``count``,
  ``memory_peak_bytes``, and with ``--trace 1`` the profiler's ``busy_s``
  and ``window_s``), with ``--trace 1`` a ``breakdown`` (the device
  operations that took most time, the longest idle gaps by what the host
  was doing), and last ``checks`` (each number compared with its limit).

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.  ``--cpu-rehearsal`` runs a shrunken copy of the cell on
the CPU in the Pallas interpreter for the tests; it never prints a result.

JAX's persistent compilation cache lives in ``$JAX_COMPILATION_CACHE_DIR``
when set, else in ``<checkout>/.cache/bench-jax``.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "src"))

from bench import harness  # noqa: E402

REHEARSAL_HW = 16
REHEARSAL_CH = 4
REHEARSAL_BATCH = 2


def shrink(config: dict, hw: int = REHEARSAL_HW, ch: int = REHEARSAL_CH):
    """A copy of the configuration small enough for a CPU: input size
    capped at ``hw`` (half that for unchained layers; a chain re-derived
    layer by layer so that it still chains), channels at ``ch`` (0: as
    configured), filters clamped to the input.  The loop's own
    ``rehearsal`` caps the traffic's batch."""
    config = json.loads(json.dumps(config))
    cap = (lambda c: min(c, ch)) if ch else (lambda c: c)
    size = min(config["layers"][0]["in_hw"], hw)
    ic = cap(config["layers"][0]["IC"])
    for layer in config["layers"]:
        if not config.get("chained"):
            size = min(layer["in_hw"], hw // 2)
            ic = cap(layer["IC"])
        f = min(layer["flt"], size)
        layer.update(in_hw=size, IC=ic, OC=cap(layer["OC"]), flt=f,
                     pad=min(layer["pad"], f - 1))
        size = (size + 2 * layer["pad"] - f) // layer["stride"] + 1
        ic = layer["OC"]
    return config


def enable_compile_cache() -> str:
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".cache", "bench-jax"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def context(bench: harness.Bench, workload: str, seed: int,
            seconds: float, *, rehearsal: bool = False, t0: float = None,
            trace_dir: str = None,
            rehearsal_size=(REHEARSAL_HW, REHEARSAL_CH)):
    """The cell's entry and what its loop needs to run it once; a
    rehearsal shrinks the configuration to ``rehearsal_size`` (input size
    cap, channel cap; see ``shrink``)."""
    import jax

    from bench import work

    cell = bench.workload(workload)
    config = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    loop = bench.loop(traffic["loop"])
    if rehearsal:
        config = shrink(config, *rehearsal_size)
        traffic = loop.rehearsal(traffic, REHEARSAL_BATCH)
    dev = jax.devices()[0]
    # A CPU rehearsal prices its work against the first chip in the table;
    # it never reports a result, so the kind is only used for arithmetic.
    kind = dev.device_kind if dev.platform == "tpu" else "TPU v5 lite"
    return cell, types.SimpleNamespace(
        config=config, traffic=traffic, seed=seed, seconds=seconds,
        chips=cell["chips"], t0=T0 if t0 is None else t0,
        trace_dir=trace_dir, peak=work.peaks(kind), loop=loop,
        reference=bench.reference(config["reference"]))


def run_cell(bench: harness.Bench, workload: str, seed: int, seconds: float,
             trace: bool, *, rehearsal: bool = False, t0: float = None,
             trace_dir: str = None,
             rehearsal_size=(REHEARSAL_HW, REHEARSAL_CH)) -> dict:
    """Run one cell once and return its result line (a dict).  The caller
    has checked the device; ``rehearsal`` shrinks the cell for a CPU."""
    import jax

    from bench import trace_reduce

    own_dir = trace and trace_dir is None
    if own_dir:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    cell, ctx = context(bench, workload, seed, seconds, rehearsal=rehearsal,
                        t0=t0, trace_dir=trace_dir if trace else None,
                        rehearsal_size=rehearsal_size)
    dev = jax.devices()[0]
    try:
        record = ctx.loop.run(ctx)
        record["chips"] = cell["chips"]
        record["peak_flops_per_s"] = ctx.peak["flops_per_s"]
        breakdown = None
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": jax.device_count(),
                  "memory_peak_bytes": record["memory_peak_bytes"]}
        if trace:
            red = trace_reduce.reduce_dir(trace_dir, chips=cell["chips"])
            record["trace"] = red
            if red is not None:
                device["busy_s"] = red["busy_s"]
                device["window_s"] = red["window_s"]
                breakdown = {"device_ops": red["device_ops"],
                             "idle_gaps": red["idle_gaps"]}
    finally:
        if own_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = bench.read_metrics(workload, trace, record)
    line = harness.result(record, metrics, device, breakdown)
    for key in ("window_compiles", "attempted", "failed", "refused",
                "errors", "buckets", "dispatches", "passes", "pass_s_min",
                "pass_s_max", "gc_collections", "gc_gen2_collections",
                "gc_pause_sum_s", "gc_pause_max_s"):
        if key in record:
            print(f"{key}: {record[key]}", file=sys.stderr)
    if record.get("late_s"):
        late = sorted(record["late_s"])
        print(f"generator lateness: median {late[len(late) // 2]:.6f} s, "
              f"max {late[-1]:.6f} s", file=sys.stderr)
    for name, c in line["checks"].items():
        ok = harness.judge({name: (c["value"], c["limit"])})
        print(f"check {name} {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if ok else 'FAIL'}", file=sys.stderr)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler's trace here (default: a "
                         "temporary directory, removed after reduction)")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="a shrunken cell on the CPU; prints no result")
    args = ap.parse_args(argv)

    bench = harness.Bench(ROOT)
    cell = bench.workload(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" and not args.cpu_rehearsal:
        print(f"bench: JAX found no TPU (platform {devs[0].platform!r})",
              file=sys.stderr)
        return 2
    if not args.cpu_rehearsal and len(devs) < cell["chips"]:
        print(f"bench: the cell needs {cell['chips']} chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        return 2
    if not args.cpu_rehearsal:
        print(f"compile cache: {enable_compile_cache()}", file=sys.stderr)
    line = run_cell(bench, args.workload, args.seed, args.seconds,
                    bool(args.trace), rehearsal=args.cpu_rehearsal,
                    trace_dir=args.trace_dir)
    if args.cpu_rehearsal:
        print(f"rehearsal: correct={line['correct']} (no result line off "
              f"the chip)", file=sys.stderr)
        return 0
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
