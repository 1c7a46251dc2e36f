"""Jit'd public wrappers around the MG3MConv Pallas kernels.

The convolution entry point is now a thin shim over ``repro.plan``: every
call builds (or is handed) a frozen ``ConvPlan`` that owns schedule
resolution, spatial pre-padding, and channel/batch alignment (the paper's
"CG-level" housekeeping, §4.1 — the TPU analogue of its 16 remainder-case
kernels).  The legacy per-call signature is preserved exactly, including its
per-call resolution semantics — callers that want plan-once / execute-many
amortization should build plans via ``repro.plan.make_plan`` /
``PlanRegistry`` instead.
"""
from __future__ import annotations

from typing import Union

import jax

from repro.core.mapping import ScheduleChoice
from repro.core.scene import ConvScene, round_up
from repro.plan import build as plan_build
from repro.plan.build import _pad_axis

ScheduleSpec = Union[None, str, ScheduleChoice]


def resolve_choice(scene: ConvScene, schedule: ScheduleSpec
                   ) -> ScheduleChoice:
    """Schedule-spec resolution shared by every conv entry point.

      None          multi-grained selection under the active cost model
                    (calibrated when an artifact exists, else roofline);
      "auto"        tuned-cache resolution with analytic fallback —
                    never measures on the hot path (see repro.tune);
      "TB11"/...    forced schedule, model-chosen blocks; raises if the
                    forced grain cannot fit VMEM (never substitutes another);
      ScheduleChoice  used exactly as given (the tuner's measurement path).

    Delegates to ``repro.plan.build.resolve_policy`` — the same resolution a
    ``ConvPlan`` runs once at build time.
    """
    return plan_build.resolve_policy(scene, schedule)


def mg3m_conv_op(inp: jax.Array, flt: jax.Array, scene: ConvScene, *,
                 schedule: ScheduleSpec = None,
                 use_pallas: bool = True) -> jax.Array:
    """Multi-grained convolution in the paper's layouts (per-call shim).

    Args:
      inp: [inH, inW, IC, B]; flt: [fltH, fltW, IC, OC].
      schedule: force "TB11"/"TB18"/"TB88"; None = analytic auto-select;
        "auto" = tuned-cache resolution (repro.tune) with analytic fallback;
        a ScheduleChoice pins the exact (schedule, bm, bn, bk).
      use_pallas: False routes to the pure-jnp reference (used by the
        distributed model code on CPU-only dry-runs).
    Returns: [outH, outW, OC, B].

    Resolution runs on *every* call (the legacy contract — ``schedule="auto"``
    callers observe a tune-cache consultation per call).  Build a plan once
    with ``repro.plan.make_plan`` to amortize it.
    """
    if inp.shape != scene.in_shape():
        raise ValueError(
            f"input shape {inp.shape} does not match the scene's IN layout "
            f"{scene.in_shape()} for {scene.describe()}")
    if flt.shape != scene.flt_shape():
        raise ValueError(
            f"filter shape {flt.shape} does not match the scene's FLT layout "
            f"{scene.flt_shape()} for {scene.describe()}")
    plan = plan_build.make_plan(scene, plan_build.ConvOp.FPROP,
                                policy=schedule, use_pallas=use_pallas)
    return plan.execute(inp, flt)


def causal_conv1d_op(x: jax.Array, w: jax.Array, *, block_l: int = 256,
                     block_d: int = 256,
                     use_pallas: bool = True) -> jax.Array:
    """Depthwise causal conv1d (Mamba2's conv) — see kernels/causal_conv1d.py."""
    from repro.kernels import causal_conv1d, ref
    if not use_pallas:
        return ref.causal_conv1d_ref(x, w)
    b, l, d = x.shape
    bl = min(block_l, l)
    bd = min(block_d, d)
    lp, dp = round_up(l, bl), round_up(d, bd)
    x_a = _pad_axis(_pad_axis(x, 1, lp), 2, dp)
    w_a = _pad_axis(w, 1, dp)
    out = causal_conv1d.causal_conv1d(x_a, w_a, block_l=bl, block_d=bd)
    return out[:, :l, :d]
