"""Public MG3MConv API — the paper's contribution as a composable JAX module.

A convolution runs through a frozen ``ConvPlan``: ``make_plan(scene, op,
policy=...)`` resolves the schedule, reads the tune cache and derives the
padded shapes exactly once, then ``plan.execute`` runs per batch (see
``repro.plan``).  ``mg3m_conv_nhwc`` is the one convenience entry for
framework-layout (NHWC) callers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.mapping import (ClassCorrection, CostModel, ScheduleChoice,
                                predicted_efficiency, select_schedule)
from repro.core.scene import ConvScene
from repro.plan import (ConvOp, ConvPlan, PlanRegistry, default_registry,
                        get_plan, make_plan, set_default_registry)
from repro.plan.build import PolicySpec

__all__ = ["ConvScene", "CostModel", "ClassCorrection", "ScheduleChoice",
           "select_schedule",
           "ConvOp", "ConvPlan", "PlanRegistry", "make_plan", "get_plan",
           "default_registry", "set_default_registry",
           "mg3m_conv_nhwc", "predicted_efficiency"]


def mg3m_conv_nhwc(x: jax.Array, flt: jax.Array, *, stride=(1, 1),
                   padding=(0, 0), schedule: PolicySpec = None) -> jax.Array:
    """Framework-friendly NHWC entry point (x: [B,H,W,C], flt: [h,w,IC,OC]).

    Transposes into the paper's [H,W,C,B] layout (a one-time layout choice in
    a real model — the paper argues B/IC/OC belong in the minor dims), runs
    the scene's fprop plan (``schedule`` is its ``make_plan`` policy), and
    transposes back to NHWC.
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
    out = make_plan(scene, policy=schedule).execute(inp, flt)
    return jnp.transpose(out, (3, 0, 1, 2))  # [B, outH, outW, OC]
