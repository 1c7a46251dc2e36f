"""Public MG3MConv API — the paper's contribution as a composable JAX module.

Two usage modes:

  * plan-once / execute-many (preferred for any repeated shape): build a
    frozen ``ConvPlan`` via ``make_plan(scene, op, policy=...)`` — schedule
    resolution, tune-cache IO, and padded-shape derivation run exactly once
    — then call ``plan.execute`` per batch (see ``repro.plan``);
  * the legacy per-call functions below, preserved as thin shims over the
    same plan machinery.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.mapping import (ClassCorrection, CostModel, ScheduleChoice,
                                predicted_efficiency, select_schedule)
from repro.core.scene import ConvScene
from repro.kernels import ops, ref
from repro.kernels.ops import ScheduleSpec
from repro.plan import (ConvOp, ConvPlan, PlanRegistry, default_registry,
                        get_plan, make_plan, set_default_registry)

__all__ = ["ConvScene", "CostModel", "ClassCorrection", "ScheduleChoice",
           "ScheduleSpec", "select_schedule",
           "ConvOp", "ConvPlan", "PlanRegistry", "make_plan", "get_plan",
           "default_registry", "set_default_registry",
           "mg3m_conv", "mg3m_conv_nhwc", "mg3m_conv_trainable",
           "predicted_efficiency"]


def __getattr__(name):
    if name == "mg3m_conv_trainable":   # lazy: avoids an import cycle
        from repro.core.autodiff import mg3m_conv_trainable
        return mg3m_conv_trainable
    raise AttributeError(name)


def mg3m_conv(inp: jax.Array, flt: jax.Array, scene: ConvScene, *,
              schedule: ScheduleSpec = None,
              use_pallas: bool = True) -> jax.Array:
    """Convolution in the paper's layouts IN[H,W,IC,B], FLT[h,w,IC,OC].

    ``schedule`` accepts None (analytic selection), "auto" (tuned-cache
    resolution with analytic fallback), a forced "TB11"/"TB18"/"TB88", or an
    exact ScheduleChoice.  Per-call shim — see ``make_plan`` to amortize
    resolution over many executions."""
    return ops.mg3m_conv_op(inp, flt, scene, schedule=schedule,
                            use_pallas=use_pallas)


def mg3m_conv_nhwc(x: jax.Array, flt: jax.Array, *, stride=(1, 1),
                   padding=(0, 0), schedule: ScheduleSpec = None,
                   use_pallas: bool = True) -> jax.Array:
    """Framework-friendly NHWC entry point (x: [B,H,W,C], flt: [h,w,IC,OC]).

    Transposes into the paper's [H,W,C,B] layout (a one-time layout choice in
    a real model — the paper argues B/IC/OC belong in the minor dims), runs
    MG3MConv, and transposes back to NHWC.
    """
    b, h, w, c = x.shape
    fh, fw, ic, oc = flt.shape
    if ic != c:
        raise ValueError(
            f"filter expects {ic} input channels but x has {c} "
            f"(x {x.shape}, flt {flt.shape})")
    scene = ConvScene(B=b, IC=c, OC=oc, inH=h, inW=w, fltH=fh, fltW=fw,
                      padH=padding[0], padW=padding[1],
                      stdH=stride[0], stdW=stride[1], dtype=str(x.dtype))
    inp = jnp.transpose(x, (1, 2, 3, 0))  # [H, W, C, B]
    out = mg3m_conv(inp, flt, scene, schedule=schedule,
                    use_pallas=use_pallas)
    return jnp.transpose(out, (3, 0, 1, 2))  # [B, outH, outW, OC]
