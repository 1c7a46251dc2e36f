"""A closed loop of passes.  One pass runs every layer of the configuration
once at ``batch`` through ``ConvPlan.execute`` and ends in
``block_until_ready``; the next pass starts when it has ended.

Traffic parameters: ``batch``.

After the window, every layer's output of its last pass is compared with
the configuration's plain reference at float32, layer by layer so that
the reference fits beside the program's state.
"""
from __future__ import annotations

import time

import jax

from bench import common, work


def rehearsal(traffic: dict, batch: int) -> dict:
    """The traffic of a CPU rehearsal: the batch capped at ``batch``."""
    return dict(traffic, batch=min(traffic["batch"], batch))


def run(ctx) -> dict:
    from repro.plan import make_plan

    cfg, batch = ctx.config, ctx.traffic["batch"]
    layers, dtype = cfg["layers"], cfg["dtype"]
    scenes = [common.scene_of(l, batch, dtype) for l in layers]
    k_w, k_x = jax.random.split(common.seed_key(ctx.seed))
    ws = common.he_weights(k_w, layers, dtype)
    xs = common.normal_arrays(k_x, tuple(sc.in_shape() for sc in scenes),
                              dtype)
    plans = [make_plan(sc) for sc in scenes]
    outs = [None] * len(plans)

    def one_pass():
        for i, (p, x, w) in enumerate(zip(plans, xs, ws)):
            outs[i] = None          # free the last pass's output first
            outs[i] = p.execute(x, w)
        jax.block_until_ready(outs)

    one_pass()                      # compiles, or loads from the cache
    passes = 0
    pass_s = []
    with common.CompileCounter() as cc, common.GcLog() as gcl, \
            common.traced(ctx.trace_dir):
        t_start = time.perf_counter()
        setup_s = t_start - ctx.t0
        t = t_start
        while True:
            one_pass()
            passes += 1
            t_end = time.perf_counter()
            pass_s.append(t_end - t)
            t = t_end
            if t_end - t_start >= ctx.seconds:
                break
    mem = common.memory_peak_bytes(ctx.chips)

    rows = work.config_least_time(cfg, batch, ctx.peak)
    rec = {
        "setup_s": setup_s, "window_s": t_end - t_start,
        "window_compiles": cc.n, "memory_peak_bytes": mem,
        "attempted": passes, "failed": 0,
        "passes": passes, "images": passes * batch,
        "pass_s_min": min(pass_s), "pass_s_max": max(pass_s),
        "useful_flops": passes * sum(r[1] for r in rows),
        "least_s": passes * sum(r[3] for r in rows),
        **gcl.summary(),
    }

    ref = ctx.reference
    limit = cfg["correct"]["max_rel_err"]
    checks = {}
    for i, layer in enumerate(layers):
        fn = jax.jit(lambda x, w, i=i: ref.layer_output(cfg, i, x, w,
                                                        "highest"))
        err = common.rel_err(outs[i], fn(xs[i], ws[i]))
        outs[i] = xs[i] = None
        common.check(checks, f"{layer['name']}_rel_err", err, limit)
    rec["checks"] = checks
    return rec
