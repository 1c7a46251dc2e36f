"""An open loop of single-image requests sent to
``ConvScheduler.session(<net>).submit`` on a Poisson schedule.

Traffic parameters: ``rate_per_s``, ``max_batch``, ``strict``.  Every seed
sends ``round(rate * seconds)`` requests whose gaps are the same set of
exponential quantiles, shuffled by the seed, so seeds differ in order and
not in load.  A request is timed from the moment it was due to the moment
its output is ready on the device; one that fails, is refused or never
comes counts as failed.

After the window, the final output of every request answered is compared
with the configuration's plain reference chain at float32, in blocks of
images.
"""
from __future__ import annotations

import math
import queue
import threading
import time

import numpy as np

import jax
import jax.numpy as jnp

from bench import common, work

# How long past the window's close the loop waits for answers; one that
# has not come by then counts as failed.
DRAIN_S = 60.0
# Images the reference runs at once, after the window.
REF_BLOCK = 32


def rehearsal(traffic: dict, batch: int) -> dict:
    """The traffic of a CPU rehearsal: serving buckets capped at
    ``batch``."""
    return dict(traffic, max_batch=min(traffic["max_batch"], batch))


def arrival_gaps(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Gaps between ``round(rate * seconds)`` requests: the exponential
    quantiles at ``(k + 0.5) / n``, the same set for every seed, in the
    seed's order."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    return np.random.default_rng(seed % 2 ** 64).permutation(gaps)


def arrival_offsets(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times, in seconds after the window opens, of every request."""
    gaps = arrival_gaps(rate, seconds, seed)
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


@jax.jit
def _worst_rel_err(got, want):
    """Per image (last axis), max |got - want| over max |want|, the worst
    of them.  An image whose output is all but zero (every unit cut by the
    last ReLU) is measured against the block's median image instead."""
    axes = tuple(range(got.ndim - 1))
    err = jnp.max(jnp.abs(got - want), axis=axes)
    scale = jnp.max(jnp.abs(want), axis=axes)
    return jnp.max(err / jnp.maximum(scale, jnp.median(scale)))


def worst_rel_err(ctx, ws, imgs, outs, idx, precision="highest"):
    """The worst error of the answers ``outs[i]``, ``i`` in ``idx``,
    against the reference chain, ``REF_BLOCK`` images at a time (the last
    block padded with repeats so that one program serves all).  NaN when
    any reading is NaN or nothing was answered."""
    if not idx:
        return math.nan
    cfg, ref = ctx.config, ctx.reference
    fn = jax.jit(lambda x, w: ref.chain_output(cfg, x, w, precision))
    worst = 0.0
    for s in range(0, len(idx), REF_BLOCK):
        part = list(idx[s:s + REF_BLOCK])
        part += [part[0]] * (REF_BLOCK - len(part))
        want = fn(jnp.stack([imgs[i] for i in part], axis=-1), ws)
        got = jnp.stack([outs[i] for i in part], axis=-1)
        e = float(_worst_rel_err(got, want))
        if math.isnan(e):
            return e
        worst = max(worst, e)
    return worst


def run(ctx) -> dict:
    from repro.serve.sched import ConvScheduler, Overloaded, SchedConfig

    cfg, tr = ctx.config, ctx.traffic
    layers, dtype = cfg["layers"], cfg["dtype"]
    offsets = arrival_offsets(tr["rate_per_s"], ctx.seconds, ctx.seed)
    n = len(offsets)
    first = layers[0]
    k_w, k_x = jax.random.split(common.seed_key(ctx.seed))
    ws = common.he_weights(k_w, layers, dtype)
    imgs = list(jnp.unstack(common.normal_arrays(
        k_x, ((n, first["in_hw"], first["in_hw"], first["IC"]),),
        dtype)[0]))
    names = [f"{cfg['name']}/{l['name']}" for l in layers]
    scenes = {nm: common.scene_of(l, 1, dtype)
              for nm, l in zip(names, layers)}
    records = []
    sched = ConvScheduler(max_batch=tr["max_batch"], strict=tr["strict"],
                          config=SchedConfig(), on_dispatch=records.append)
    act = {None: None, "relu": jax.nn.relu}[cfg["activation"]]
    sched.register_net(cfg["name"], scenes, dict(zip(names, ws)),
                       activation=act)
    sched.prewarm(compile=True)
    sess = sched.session(cfg["name"])
    # Every group size the window can form, so that the glue around the
    # plans (concat, pad, activation, lane slices) compiles here.
    for g in range(1, tr["max_batch"] + 1):
        rs = [sess.submit(imgs[i % n]) for i in range(g)]
        sched.drain()
        jax.block_until_ready([r.out for r in rs])

    done_t = [math.inf] * n
    outs = [None] * n
    errors = []
    pending: "queue.Queue" = queue.Queue()

    def waiter():
        while True:
            item = pending.get()
            if item is None:
                return
            i, r = item
            try:
                out = sched.wait([r])[0]
                jax.block_until_ready(out)
            except Exception as e:  # noqa: BLE001 - a failed request is
                errors.append(repr(e))  # counted, not fatal to the run
                continue
            done_t[i] = time.perf_counter()
            outs[i] = out

    sched.start()
    refused = 0
    late = []
    th = threading.Thread(target=waiter, name="bench-waiter", daemon=True)
    th.start()
    try:
        with common.CompileCounter() as cc, common.GcLog() as gcl, \
                common.traced(ctx.trace_dir):
            snap0 = sched.snapshot()
            rec0 = len(records)
            t_start = time.perf_counter()
            setup_s = t_start - ctx.t0
            due = t_start + offsets
            for i in range(n):
                wait = due[i] - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                late.append(time.perf_counter() - due[i])
                try:
                    pending.put((i, sess.submit(imgs[i])))
                except Overloaded:
                    refused += 1
            pending.put(None)
            th.join(timeout=max(0.0, t_start + ctx.seconds + DRAIN_S
                                - time.perf_counter()))
            t_end = time.perf_counter()
            snap1 = sched.snapshot()
            rec1 = len(records)
    finally:
        sched.stop()
    if th.is_alive():
        errors.append(f"answers still missing {DRAIN_S:.0f} s after the "
                      f"window closed")
    mem = common.memory_peak_bytes(ctx.chips)

    latencies = [done_t[i] - due[i] for i in range(n)]
    served = [i for i in range(n) if outs[i] is not None]
    flops_1 = sum(work.layer_flops(l, 1) for l in layers)

    def least(b):
        return sum(work.least_time(work.layer_flops(l, b),
                                   work.layer_bytes(l, b, dtype),
                                   ctx.peak)[0] for l in layers)

    window = records[rec0:rec1]

    def delta(name, field):
        """A counter's or histogram's field over the window, from the
        program's snapshots taken at its edges."""
        return (snap1.get(name, {}).get(field, 0.0)
                - snap0.get(name, {}).get(field, 0.0))
    rec = {
        "setup_s": setup_s, "window_s": t_end - t_start,
        "window_compiles": cc.n, "memory_peak_bytes": mem,
        "attempted": n, "failed": n - len(served), "refused": refused,
        "errors": errors[:5],
        "latencies_s": latencies,
        # a failed request lies beyond every served one: it is counted at
        # the time the run stopped waiting for it
        "give_up_s": t_end - t_start,
        "late_s": late,
        "useful_flops_done": flops_1 * len(served),
        "latency_sum_done_s": sum(latencies[i] for i in served),
        "dispatches": len(window),
        "least_s": sum(least(r.occupied) for r in window),
        "useful_flops": flops_1 * sum(r.occupied for r in window),
        "queue_wait_sum_s": delta("repro.serve.queue_wait_s", "sum"),
        "queue_wait_count": delta("repro.serve.queue_wait_s", "count"),
        "occupied_lanes": delta("repro.serve.occupied_lanes", "value"),
        "bucket_lanes": delta("repro.serve.bucket_lanes", "value"),
        "buckets": sorted({r.bucket for r in window}),
        **gcl.summary(),
    }

    checks = {}
    common.check(checks, "worst_rel_err",
                 worst_rel_err(ctx, ws, imgs, outs, served),
                 cfg["correct"]["max_rel_err"])
    rec["checks"] = checks
    return rec
