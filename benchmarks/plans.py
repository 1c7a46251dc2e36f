"""Plan-amortized dispatch overhead: legacy per-call resolution vs
plan-once / execute-many — plus the dilated-dgrad table.

For each scene the first table wall-clocks (a) the legacy ``mg3m_conv_op``
shim, which re-runs schedule resolution and shape derivation on every call,
and (b) ``plan.execute`` on a plan built once, which dispatches straight
into the jitted kernel.  The difference is the per-call dispatch overhead a
serving process amortizes away by warm-starting a ``PlanRegistry``.

The ``dgrad_*`` rows compare the two ways a strided forward's input
gradient can run: the dilated-Pallas MG3M scene (sentinel index maps over
the compact dOUT) vs the jnp-reference adjoint that used to be the
recorded fallback.  Wall times follow the ``benchmarks/common.py`` honesty
conventions — CPU-interpret Pallas vs native XLA is *not* a like-for-like
wall-clock comparison on this container, so both wall clocks are reported
but the speedup axis is the cost model's (the repo's paper-scale truth
axis): the fallback's algorithm is a transposed conv over a materialized
lhs-dilated scatter, so ``pred_ref_scatter`` prices exactly that —
zero-interleave dOUT (one HBM round trip for the ``std^2``-inflated
buffer) plus the dense conv over it — and ``pred_speedup`` is how much the
sentinel-route dgrad, which never materializes the scatter, beats it.
"""
import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit
from repro.core.mapping import HBM_BW, select_schedule
from repro.core.scene import ConvScene
from repro.kernels import ops
from repro.plan import ConvOp, make_plan
from repro.tune.measure import make_operands

# Small scenes: interpret-mode kernel time stays low enough that the
# per-call dispatch overhead is visible in the totals.
_SCENES = {
    "tiny": ConvScene(B=4, IC=8, OC=8, inH=6, inW=6, fltH=3, fltW=3,
                      padH=1, padW=1),
    "pointwise": ConvScene(B=8, IC=16, OC=16, inH=5, inW=5, fltH=1, fltW=1),
    "strided": ConvScene(B=4, IC=8, OC=8, inH=8, inW=8, fltH=3, fltW=3,
                         padH=1, padW=1, stdH=2, stdW=2),
}


def _time_us(fn, iters):
    jax.block_until_ready(fn())      # warmup/compile outside the clock
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / iters * 1e6


# Paper-scale strided layers (ResNet stage entry / projection shortcut /
# VGG-ish downsample).  Per ``bench_scene``'s convention the derived model
# metrics use the FULL scene; the wall clock times a channel/batch-capped
# instance a 1-core CPU can turn around.
_DGRAD_SCENES = {
    "res3x3_s2": ConvScene(B=32, IC=64, OC=128, inH=56, inW=56, fltH=3,
                           fltW=3, padH=1, padW=1, stdH=2, stdW=2),
    "proj1x1_s2": ConvScene(B=32, IC=64, OC=128, inH=56, inW=56, fltH=1,
                            fltW=1, stdH=2, stdW=2),
    "vgg3x3_s2": ConvScene(B=64, IC=128, OC=128, inH=28, inW=28, fltH=3,
                           fltW=3, padH=1, padW=1, stdH=2, stdW=2),
}


def dgrad_rows(iters: int = 5):
    """Dilated-Pallas dgrad vs the jnp-reference fallback, per module doc."""
    out = []
    for name, full in _DGRAD_SCENES.items():
        # model axis at paper scale: the sentinel route vs the fallback's
        # materialized-scatter algorithm (see module docstring)
        full_plan = make_plan(full, ConvOp.DGRAD)
        gsc, sent = full_plan.exec_scene, full_plan.choice
        interleaved = ConvScene(**{**gsc.__dict__,
                                   "inH": gsc.dilated_inH,
                                   "inW": gsc.dilated_inW,
                                   "dilH": 1, "dilW": 1})
        itemsize = jnp.dtype(gsc.dtype).itemsize
        scatter_s = 2 * (itemsize * interleaved.inH * interleaved.inW
                         * interleaved.IC * interleaved.B) / HBM_BW
        ref_scatter_s = select_schedule(interleaved).predicted_s + scatter_s
        pred_speedup = ref_scatter_s / sent.predicted_s
        blowup = (interleaved.inH * interleaved.inW) / (gsc.inH * gsc.inW)
        # wall clock on a capped instance (relative numbers only)
        sc = ConvScene(**{**full.__dict__, "B": min(full.B, 4),
                          "IC": min(full.IC, 8), "OC": min(full.OC, 8),
                          "inH": min(full.inH, 10), "inW": min(full.inW, 10)})
        _, flt = make_operands(sc)
        cot = jax.random.normal(jax.random.PRNGKey(7), sc.out_shape(),
                                jnp.float32)
        plan = make_plan(sc, ConvOp.DGRAD)
        ref_plan = make_plan(sc, ConvOp.DGRAD, use_pallas=False)
        pallas_us = _time_us(lambda: plan.execute(cot, flt), iters)
        ref_us = _time_us(lambda: ref_plan.execute(cot, flt), iters)
        out.append((
            f"dgrad_{name}", pallas_us,
            f"ref_fallback={ref_us:.1f}us;schedule={sent.schedule};"
            f"pred_dgrad={sent.predicted_s * 1e6:.0f}us;"
            f"pred_ref_scatter={ref_scatter_s * 1e6:.0f}us;"
            f"pred_speedup={pred_speedup:.2f}x;"
            f"scatter_blowup_avoided={blowup:.1f}x"))
    return out


def rows(iters: int = 10):
    out = []
    for name, sc in _SCENES.items():
        inp, flt = make_operands(sc)
        plan = make_plan(sc, ConvOp.FPROP)          # plan-once, off the clock
        legacy_us = _time_us(
            lambda: ops.mg3m_conv_op(inp, flt, sc), iters)
        plan_us = _time_us(lambda: plan.execute(inp, flt), iters)
        out.append((
            f"plan_{name}", plan_us,
            f"legacy_per_call={legacy_us:.1f}us;"
            f"dispatch_saving={legacy_us - plan_us:.1f}us;"
            f"schedule={plan.schedule}"))
    return out + dgrad_rows()


def main():
    emit(rows())


if __name__ == "__main__":
    main()
