"""Measurement harness: wall-clock one candidate schedule through the real
``ConvPlan.execute`` dispatch.

Off a TPU the kernels run in the Pallas interpreter, so absolute µs validate *relative*
candidate ordering, not TPU truth; on a TPU the same harness times
compiled kernels (the kernel mode follows the platform).  Proxy mode
(channel/batch/spatial caps) measures a shrunken stand-in of the scene —
every use is recorded in the tuned artifact, never silent.
"""
from __future__ import annotations

import math
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.mapping import ScheduleChoice
from repro.core.scene import ConvScene, ceil_div
from repro.obs.metrics import default_metrics
from repro.obs.trace import default_tracer

# A candidate that cannot produce one timed call inside this budget is scored
# at whatever it cost so far — bad-but-finite beats hanging the whole tune.
DEFAULT_TIMEOUT_S = 120.0


def proxy_scene(scene: ConvScene, *, measure_batch: Optional[int] = None,
                measure_max_ch: Optional[int] = None,
                measure_max_hw: Optional[int] = None) -> ConvScene:
    """Channel/batch/spatial-capped stand-in for wall-clock measurement.

    Caps shrink the grid a candidate runs over so interpret-mode timing is
    feasible on CPU — but the kernel wrapper clips blocks to the capped
    dims, so distinct full-scene candidates can alias to the same executed
    kernel here; the autotuner dedups on the clipped execution before
    measuring.  The cap keeps the filter window valid.
    """
    d = dict(scene.__dict__)
    if measure_batch:
        d["B"] = min(scene.B, measure_batch)
    if measure_max_ch:
        d["IC"] = min(scene.IC, measure_max_ch)
        d["OC"] = min(scene.OC, measure_max_ch)
    if measure_max_hw:
        # Smallest input that still yields one output pixel: the *dilated*
        # input plus padding must cover the *dilated* filter footprint
        # (stride only affects how many *more* pixels fit), and a proxy must
        # never be larger than the scene it stands in for.
        need_h = scene.dilated_fltH - 2 * scene.padH - scene.apadH
        need_w = scene.dilated_fltW - 2 * scene.padW - scene.apadW
        min_h = 1 + max(ceil_div(need_h - 1, scene.dilH), 0)
        min_w = 1 + max(ceil_div(need_w - 1, scene.dilW), 0)
        d["inH"] = min(scene.inH, max(measure_max_hw, min_h))
        d["inW"] = min(scene.inW, max(measure_max_hw, min_w))
    return ConvScene(**d)


def make_operands(scene: ConvScene, seed: int = 0):
    """Random IN/FLT in the scene's paper layouts and dtype."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    dt = jnp.dtype(scene.dtype)
    inp = jax.random.normal(k1, scene.in_shape(), jnp.float32).astype(dt)
    flt = jax.random.normal(k2, scene.flt_shape(), jnp.float32).astype(dt)
    return inp, flt


def measure_choice(scene: ConvScene, choice: ScheduleChoice, *,
                   iters: int = 3, warmup: int = 1,
                   timeout_s: float = DEFAULT_TIMEOUT_S) -> float:
    """Median wall-time (µs) of the scene's fprop plan pinned to ``choice``
    (``make_plan(scene, policy=choice).execute``), plan build included in
    every call as the tuner has always timed it.

    Warmup triggers compilation; the remaining budget bounds how many timed
    iterations actually run.  The budget applies to warmup too: a candidate
    that burns the whole ``timeout_s`` before producing a single timed call
    scores ``inf`` (like an infeasible one) rather than hanging a batch tune
    arbitrarily past its deadline.  An infeasible candidate (compile/shape
    failure) likewise scores ``inf`` so the picker skips it instead of
    aborting the tune.
    """
    from repro.plan.build import make_plan  # local: avoids an import cycle

    m = default_metrics()
    m.counter("repro.tune.measurements").inc()
    inp, flt = make_operands(scene)
    with default_tracer().span("repro.tune.measure",
                               schedule=choice.schedule, bm=choice.bm,
                               bn=choice.bn, bk=choice.bk,
                               scene=scene.describe()) as sp:
        t0 = time.perf_counter()
        try:
            fn = lambda: make_plan(scene, policy=choice).execute(inp, flt)
            for _ in range(max(warmup, 1)):
                jax.block_until_ready(fn())
                if time.perf_counter() - t0 > timeout_s:
                    # budget exhausted before any timed iteration
                    m.counter("repro.tune.measure_timeouts").inc()
                    sp.set(outcome="timeout")
                    return math.inf
            times = []
            for _ in range(max(iters, 1)):
                t1 = time.perf_counter()
                jax.block_until_ready(fn())
                times.append(time.perf_counter() - t1)
                if time.perf_counter() - t0 > timeout_s:
                    break
            times.sort()
            us = times[len(times) // 2] * 1e6
            m.histogram("repro.tune.measure_s").observe(us * 1e-6)
            sp.set(outcome="ok", measured_us=us)
            return us
        except Exception:  # noqa: BLE001 — kernel failure = infeasible point
            m.counter("repro.tune.measure_failures").inc()
            sp.set(outcome="infeasible")
            return math.inf
