"""The control of a training cell's comparison: the plain reference at the
step below float32 (three bf16 passes, ``"high"``) planted in the
program's place in all three conv directions, so that a whole run of the
cell goes through its own loop and its own judgement with it.  Such a run
has to come out ``correct: false``.

The forward is ``control.planted``'s, given a plan-layout view of the
reference's NHWC convolution; dgrad and wgrad are that convolution's
input and filter gradients at the same passes.

    with planted(bench.reference(config["reference"])):
        line = run.run_cell(...)
"""
from __future__ import annotations

import contextlib
import functools
import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import control  # noqa: E402


def _plan_conv(reference):
    """``conv(x, w, layer, precision)`` over plan-layout ``x`` ``[H, W, C,
    B]`` by way of the reference's NHWC convolution."""
    import jax.numpy as jnp

    def conv(x, w, layer, precision):
        y = reference.conv(jnp.transpose(x, (3, 0, 1, 2)), w, layer,
                           precision)
        return jnp.transpose(y, (1, 2, 3, 0))
    return conv


@contextlib.contextmanager
def planted(reference):
    """Every ``ConvPlan.execute`` in the block, forward and backward,
    computes with the reference at ``control.PRECISION``."""
    import jax
    import jax.numpy as jnp

    from repro.plan.build import ConvOp, ConvPlan

    conv = _plan_conv(reference)

    @functools.partial(jax.jit, static_argnames=("geom", "op", "shape"))
    def backward(a, b, geom, op, shape):
        layer = {"pad": geom[0], "stride": geom[1]}
        zero = jnp.zeros(shape, a.dtype)
        if op == "dgrad":          # (d_out, flt) -> d_in
            _, vjp = jax.vjp(lambda x: conv(x, b, layer, control.PRECISION),
                             zero)
            return vjp(a)[0]
        _, vjp = jax.vjp(lambda w: conv(a, w, layer, control.PRECISION),
                         zero)     # (inp, d_out) -> d_flt
        return vjp(b)[0]

    with control.planted(types.SimpleNamespace(conv=conv)):
        forward = ConvPlan.execute

        def execute(self, a, b):
            if self.op is ConvOp.FPROP:
                return forward(self, a, b)
            sc = self.scene
            return backward(a, b, (sc.padH, sc.stdH), self.op.value,
                            self.io_shapes()[2])

        ConvPlan.execute = ConvPlan.__call__ = execute
        yield


def main(argv=None) -> int:
    """The control's readings on the chip, one run a seed in one process:

        python3 bench/control_train.py --workload <cell> --seeds 1,2,3 \\
            --seconds <s>

    prints one JSON line a run (seed, readings, ``correct``) and, last, the
    smallest reading of each number and whether every run came out not
    correct.  ``limits.py --seeds`` gives the program's side."""
    import argparse
    import json

    from bench import harness
    from bench import limits
    from bench import run as bench_run

    ap = argparse.ArgumentParser(description=main.__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=limits.seeds, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)

    import jax
    if jax.devices()[0].platform != "tpu" and not args.cpu_rehearsal:
        print("control_train: JAX found no TPU", file=sys.stderr)
        return 2
    if not args.cpu_rehearsal:
        bench_run.enable_compile_cache()
    bench = harness.Bench(bench_run.ROOT)
    cfg = bench.config(bench.workload(args.workload)["config"])
    least, all_failed = {}, True
    for seed in args.seeds:
        with planted(bench.reference(cfg["reference"])):
            line = bench_run.run_cell(bench, args.workload, seed,
                                      args.seconds, False,
                                      rehearsal=args.cpu_rehearsal)
        readings = {k: c["value"] for k, c in line["checks"].items()}
        print(json.dumps({"side": "control", "seed": seed,
                          "correct": line["correct"],
                          "readings": readings}), flush=True)
        all_failed &= not line["correct"]
        for k, v in readings.items():
            least[k] = min(least.get(k, v), v)
    print(json.dumps({"workload": args.workload, "control_min": least,
                      "control_judged_not_correct": all_failed}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
