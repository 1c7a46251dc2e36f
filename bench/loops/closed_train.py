"""A closed loop of training steps through the program's own training path.

One step is ``train.cnn.build_cnn_train_step`` over a ``ModelPlans`` of
every conv (fprop, dgrad and wgrad each a ``ConvPlan``) and the layer
graph of a bottleneck ResNet v1.5 (``models.cnn.resnet_scenes`` and
``resnet_graph``): forward, backward and the AdamW update, compiled once
before the window.  Each step is enqueued under a ``repro.train.step``
span and ends in ``block_until_ready`` on its loss; the next starts when
it has ended.  Steps cycle through a pool of device-resident batches made
from the seed, inside a ``resolution_guard`` (no schedule is resolved in
the window).

Traffic parameters: ``batch`` (images a step), ``pool`` (batches cycled).

The model is read from the configuration: the image size and every
channel width from its ``layers`` (so that a rehearsal's ``shrink`` shrinks
the model), the blocks a stage from ``stages``.

After the window the loop keeps a copy of the state and runs the same
compiled step once more from it, on the next batch of the pool, and
compares that step with the configuration's plain reference (float32 at
``HIGHEST``) from the same state and batch:

``loss_rel_err``        the loss;
``worst_grad_rel_err``  the largest ``max|got - want| / max|want|`` over
                        the parameter tensors, the program's gradients
                        being those its update applied;
``update_rel_err``      the largest ``max|got - want|`` of the updated
                        parameters over ``max|want - before|``, ``want``
                        being the reference's AdamW applied to the
                        program's own gradients: the optimizer checked on
                        its own, so that a step left undone reads 1.

A ReLU net's gradients hold the masks its forward drew, and rounding at
float32 flips a few activations that sit next to zero: each flip moves a
stage-4 weight gradient by about ``1/sqrt(7 * 7 * B)`` of its largest
element.  So the gradient readings have a floor that the reference's own
float32 rounding reaches too, and the limits sit between that floor and
the readings of the reference one step below float32 (PERF.md section 2).

The compiled step and the reference's compiled functions are kept for the
process, so that ``limits.py`` pays set-up once over its seeds.  The step
is keyed by the model and by the plan executor and the optimizer update
in place when it was traced: those are what a planted control or a
planted fault replaces.

With ``--trace 1`` the loop also reads the trace itself
(``trace_scopes.py``): device time under the graph's ``repro.graph.bn``,
``.pool`` and ``.add`` scopes, and of the conv kernels per direction.  The
scope path of a device operation is the ``op_name`` metadata of its
instruction in the compiled step's HLO text.  On a TPU v5e an op event of
the device trace carries that instruction's HLO text as its name and only
timing in its stats (``device_offset_ps``, ``device_duration_ps``), so the
scope is found through the instruction's name.
"""
from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp

from bench import common, trace_scopes, work


REHEARSAL_MIN_BATCH = 128
_COMPILED = {}


def rehearsal(traffic: dict, batch: int) -> dict:
    """The traffic of a CPU rehearsal: the batch capped at ``batch``, but
    at no fewer than ``REHEARSAL_MIN_BATCH`` images: batch norm's
    statistics over a shrunken 1x1 last stage are over the batch alone,
    and two samples a channel normalise to +-1 whatever came before."""
    return dict(traffic, batch=min(traffic["batch"],
                                   max(batch, REHEARSAL_MIN_BATCH)))


def model(cfg: dict, batch: int):
    """(scenes, graph) of the configuration's ResNet at ``batch``; raises
    unless the program's scenes have the geometry the configuration
    lists (and, unshrunk, its input size)."""
    from repro.models import cnn as M

    layers = {l["name"]: l for l in cfg["layers"]}
    stem = layers["stem"]
    widths = tuple(layers[f"s{i}b0.a"]["OC"]
                   for i in range(1, len(cfg["stages"]) + 1))
    scenes = M.resnet_scenes(
        batch, stem["in_hw"], in_ch=stem["IC"], stem=stem["OC"],
        widths=widths, blocks=tuple(s["blocks"] for s in cfg["stages"]),
        expansion=layers["s1b0.c"]["OC"] // widths[0], dtype=cfg["dtype"])
    keys = ("IC", "OC", "flt", "pad", "stride")
    if stem["in_hw"] == cfg["input"][0]:
        keys += ("in_hw",)
    got = {n: dict(zip(keys, (sc.IC, sc.OC, sc.fltH, sc.padH, sc.stdH,
                              sc.inH))) for n, sc in scenes.items()}
    want = {n: {k: l[k] for k in keys} for n, l in layers.items()}
    if got != want:
        raise ValueError("the program's ResNet scenes differ from the "
                         "configuration's layers")
    return scenes, M.resnet_graph(tuple(s["blocks"] for s in cfg["stages"]))


def layer_rows(scenes) -> list:
    """Config-style layer entries of the scenes actually run."""
    return [{"name": n, "IC": sc.IC, "OC": sc.OC, "in_hw": sc.inH,
             "flt": sc.fltH, "pad": sc.padH, "stride": sc.stdH}
            for n, sc in scenes.items()]


def step_work(layers, batch: int, classes: int, dtype: str, peak: dict):
    """(useful FLOPs, least seconds, dgrad least seconds) of one step.  Each
    conv's fprop, dgrad and wgrad move the same unpadded tensors and do
    the same multiply-adds (``work.py``); the first conv's dgrad is not
    needed and not counted.  The head's forward and two backward matmuls
    count toward the FLOPs only."""
    rows = work.config_least_time({"layers": layers, "dtype": dtype},
                                  batch, peak)
    first_flops, first_s = rows[0][1], rows[0][3]
    head = 3 * 2 * batch * layers[-1]["OC"] * classes
    dgrad_s = sum(r[3] for r in rows) - first_s
    return (3 * sum(r[1] for r in rows) - first_flops + head,
            3 * sum(r[3] for r in rows) - first_s, dgrad_s)


def opt_config(cfg: dict):
    from repro.train.optimizer import AdamWConfig
    o = cfg["optimizer"]
    return AdamWConfig(lr=o["lr"], beta1=o["beta1"], beta2=o["beta2"],
                       eps=o["eps"], weight_decay=o["weight_decay"],
                       clip_norm=o["clip_norm"],
                       warmup_steps=o["warmup_steps"],
                       total_steps=o["total_steps"],
                       min_lr_frac=o["min_lr_frac"])


def batches(key, n: int, batch: int, scene, classes: int, dtype: str):
    """``n`` batches of NHWC standard-normal images and uniform labels, made
    on the device in one call."""
    shape = (batch, scene.inH, scene.inW, scene.IC)

    @jax.jit
    def make(k):
        out = []
        for kk in jax.random.split(k, n):
            ki, kl = jax.random.split(kk)
            out.append({"images": jax.random.normal(ki, shape, jnp.float32
                                                    ).astype(dtype),
                        "labels": jax.random.randint(kl, (batch,), 0,
                                                     classes)})
        return out
    return make(key)


def _model_key(cfg: dict, batch: int) -> str:
    return json.dumps([cfg[k] for k in ("layers", "stages", "classes",
                                        "dtype", "bn_eps", "optimizer")]
                      + [batch], sort_keys=True)


def compiled_step(cfg: dict, batch: int, scenes, graph, state, first):
    """The training step over a ``ModelPlans`` of ``scenes``, compiled for
    ``state`` and a batch like ``first``; raises if a layer falls back to
    the jnp reference."""
    from repro.core.autodiff import make_model_plans
    from repro.plan.build import ConvPlan
    from repro.train import cnn as tc
    from repro.train import optimizer

    key = (_model_key(cfg, batch), ConvPlan.execute, optimizer.adamw_update)
    if key not in _COMPILED:
        plans = make_model_plans(scenes)
        if plans.reference_ops:
            raise RuntimeError(f"layers fall back to the jnp reference: "
                               f"{plans.reference_ops}")
        _COMPILED[key] = tc.jit_train_step(tc.build_cnn_train_step(
            plans, opt_config(cfg), graph=graph, with_grads=True)
        ).lower(state, first).compile()
    return _COMPILED[key]


def step_bytes(step) -> dict:
    """The compiled step's own account of its device memory (bytes)."""
    m = step.memory_analysis()
    if m is None:
        return {}
    return {k: int(getattr(m, k + "_size_in_bytes"))
            for k in ("temp", "argument", "output", "alias")}


def reference_fns(cfg: dict, ref):
    """The reference's jitted ``(loss, grads)`` at float32 and its AdamW."""
    key = (_model_key(cfg, 0), ref.__name__, "reference")
    if key not in _COMPILED:
        _COMPILED[key] = (
            jax.jit(lambda p, x, y: ref.loss_and_grads(cfg, p, x, y,
                                                       "highest")),
            jax.jit(lambda p, g, m, v, s: ref.adamw(cfg, p, g, m, v, s)))
    return _COMPILED[key]


def run(ctx) -> dict:
    from repro.models import cnn as M
    from repro.train import cnn as tc

    cfg, batch, n_pool = ctx.config, ctx.traffic["batch"], ctx.traffic["pool"]
    scenes, graph = model(cfg, batch)
    k_w, k_d = jax.random.split(common.seed_key(ctx.seed))
    params = jax.jit(lambda k: M.init_resnet(k, scenes, cfg["classes"],
                                             jnp.dtype(cfg["dtype"])))(k_w)
    state = tc.init_train_state(params)
    pool = batches(k_d, n_pool, batch, scenes["stem"], cfg["classes"],
                   cfg["dtype"])
    step = compiled_step(cfg, batch, scenes, graph, state, pool[0])
    compiled_bytes = step_bytes(step)
    print(f"step memory_analysis: {json.dumps(compiled_bytes)}",
          file=sys.stderr)
    state, out = step(state, pool[0])   # first run: the step's buffers
    jax.block_until_ready(out["loss"])

    steps = 0
    step_s = []
    with tc.resolution_guard(), common.CompileCounter() as cc, \
            common.GcLog() as gcl, common.traced(ctx.trace_dir):
        t_start = time.perf_counter()
        setup_s = t_start - ctx.t0
        t = t_start
        while True:
            state, out = tc.dispatch_step(step, state, pool[(steps + 1)
                                                            % n_pool])
            jax.block_until_ready(out["loss"])
            steps += 1
            t_end = time.perf_counter()
            step_s.append(t_end - t)
            t = t_end
            if t_end - t_start >= ctx.seconds:
                break
    mem = common.memory_peak_bytes(ctx.chips)
    out = None

    layers = layer_rows(scenes)
    flops, least_s, dgrad_s = step_work(layers, batch, cfg["classes"],
                                        cfg["dtype"], ctx.peak)
    rec = {
        "setup_s": setup_s, "window_s": t_end - t_start,
        "window_compiles": cc.n, "memory_peak_bytes": mem,
        "attempted": steps, "failed": 0,
        "passes": steps, "images": steps * batch,
        "pass_s_min": min(step_s), "pass_s_max": max(step_s),
        "useful_flops": steps * flops, "least_s": steps * least_s,
        "dgrad_least_s": steps * dgrad_s, "step_bytes": compiled_bytes,
        **gcl.summary(),
    }
    if ctx.trace_dir is not None:
        red = trace_scopes.reduce_dir(ctx.trace_dir, step.as_text(),
                                      ctx.chips)
        if red is not None:
            rec["graph_s"] = red["graph_s"]
            rec["scope_s"] = red["scope_s"]
            rec["conv_dir_s"] = red["conv_dir_s"]
            rec["dgrad_conv_s"] = red["conv_dir_s"]["dgrad"]
            print(f"device seconds by scope: {json.dumps(red['scope_s'])}"
                  f"\nconv device seconds by direction: "
                  f"{json.dumps(red['conv_dir_s'])}", file=sys.stderr)
    rec["checks"] = check(ctx, step, state, pool[(steps + 1) % n_pool])
    return rec


def check(ctx, step, state, batch) -> dict:
    """The step once more from a copy of ``state``, against the reference
    step from the same state and batch (see the module docstring)."""
    cfg, ref = ctx.config, ctx.reference
    before = jax.tree.map(jnp.copy, state)
    after, out = step(state, batch)
    got_loss, got_grads = out["loss"], out["grads"]
    del state, out
    loss_fn, adamw = reference_fns(cfg, ref)
    want_loss, want_grads = loss_fn(before.params, batch["images"],
                                    batch["labels"])
    own_update = adamw(before.params, got_grads, before.opt.m,
                       before.opt.v, before.opt.step)
    limits = cfg["correct"]
    checks = {}
    common.check(checks, "loss_rel_err",
                 abs(float(got_loss) - float(want_loss))
                 / abs(float(want_loss)), limits["loss_rel_err"])
    common.check(checks, "worst_grad_rel_err",
                 max(float(common.rel_err(got_grads[k], want_grads[k]))
                     for k in want_grads), limits["worst_grad_rel_err"])
    common.check(checks, "update_rel_err",
                 max(float(_update_err(after.params[k], own_update[k],
                                       before.params[k]))
                     for k in own_update), limits["update_rel_err"])
    return checks


@jax.jit
def _update_err(got, want, before):
    """Largest |got - want| of updated parameters over the largest step the
    reference took, in float32."""
    got, want, before = (a.astype(jnp.float32) for a in (got, want, before))
    return jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want - before))
