#!/usr/bin/env python3
"""Chip smoke test: the conv stack's main paths on a TPU, through the entry
points a user calls, at the published widths of the ResNet trunk of
``cnn_chain_scenes("resnet")`` (224 px, channels 3 -> 64 -> ... -> 512).

    python chip_smoke.py                  # one chip: serve, then train
    python chip_smoke.py --four-chips     # four chips: sharded plans and
                                          # one sharded train step, each
                                          # against the one-chip plans
    python chip_smoke.py --cpu-rehearsal  # a shrunken trunk on the CPU

Serve: a ``ConvScheduler`` prewarms and compiles every bucket of the trunk,
then seeded single images go through ``session("resnet")``, some alone and
some in one coalesced burst; every output is compared with a plain jnp
chain of ``conv_ref`` + relu at ``precision=HIGHEST``.  Train: three
plan-driven steps at B=8, with step 0's gradients compared with
``jax.grad`` of the same reference forward.  Weights, images and labels
are random from ``--seed``.

Each phase fails the run (exit 1) on a plan that falls back to the jnp
reference, on any compile inside the steady-state window, or on an error
above the tolerances below.  The last line of a passing chip run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU the script exits 2 and prints no result; ``--cpu-rehearsal``
runs every phase on a trunk capped at 16 px, 8 channels and 4 layers in
the Pallas interpreter, and never reports ok.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.autodiff import apply_conv, make_model_plans  # noqa: E402
from repro.core.mapping import select_schedule  # noqa: E402
from repro.data.pipeline import SyntheticImages  # noqa: E402
from repro.kernels.ref import conv_ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import data_devices, make_mesh_for  # noqa: E402
from repro.models.cnn import (chain_graph, cnn_chain_scenes,  # noqa: E402
                              init_cnn_from_scenes, nhwc_to_plan)
from repro.plan import ConvOp, make_plan  # noqa: E402
from repro.serve.conv import seeded_weights  # noqa: E402
from repro.serve.sched import ConvScheduler, SchedConfig  # noqa: E402
from repro.shard import (PARTITION_AXES, make_sharded_plan,  # noqa: E402
                         pinned_shard_spec, shard_blocker, shard_sub_scene)
from repro.train import cnn as tc  # noqa: E402
from repro.train.optimizer import AdamWConfig  # noqa: E402
from repro.tune.calibrate import active_cost_model  # noqa: E402

# Worst |got - want| / max|want| allowed, all in f32.  The kernels
# accumulate in f32 like the HIGHEST reference, so only the summation order
# differs: ~1e-6 through ten layers.  A kernel whose products round to
# bf16 lands near 1e-2 and fails.
SERVE_TOL = 1e-4
# Per parameter leaf.  The reference backward gates with the plan
# forward's relu masks: one pre-activation whose sign the two forwards
# disagree on (a last-bit difference at zero) would otherwise move a whole
# wgrad term, ~1e-3 of a 112x112 layer's gradient.
GRAD_TOL = 1e-4
# Four-chip plans against the one-chip plan: the ic split reorders the
# f32 reduction across chips (psum); the other axes are exact.
SHARD_TOL = 1e-4

TRAIN_BATCH = 8
MAX_BATCH = 8
N_CLASSES = 10
SINGLES = 2          # requests served alone (bucket 1)
BURST = 4            # requests submitted together (one coalesced bucket)
# Below the trunk's occupancy target a group dispatches when its oldest
# request has waited this long: long enough that the burst always lands in
# one group, so the warm-up and the measured pass dispatch the same shapes.
GATHER_S = 0.5
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result or broke a steady-state rule."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileCounter:
    """Counts XLA compiles (backend compiles and persistent-cache loads)
    while the ``with`` block runs."""

    def __init__(self):
        self.n = 0

    def _on_duration(self, event, duration_secs, **kwargs):
        if event == BACKEND_COMPILE:
            self.n += 1

    def _on_event(self, event, **kwargs):
        if event == CACHE_HIT:
            self.n += 1

    def __enter__(self) -> "CompileCounter":
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> bool:
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
        return False


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                   1e-30))


def trunk(batch: int, rehearsal: bool):
    if rehearsal:
        return cnn_chain_scenes("resnet", batch, max_hw=16, max_ch=8,
                                layers_per_net=4)
    return cnn_chain_scenes("resnet", batch)


def blocking(plan) -> str:
    if plan.uses_reference:
        return "jnp-reference"
    return f"{plan.schedule}({plan.spec.bm}/{plan.spec.bn}/{plan.spec.bk})"


def ref_chain(x, flts, scenes, masks=None):
    """Plain jnp trunk in plan layout: conv_ref (HIGHEST) + relu per layer;
    with ``masks`` the relu gates on those sign patterns instead."""
    for name, sc in scenes.items():
        y = conv_ref(x, flts[name], sc)
        x = jax.nn.relu(y) if masks is None else jnp.where(masks[name], y, 0.)
    return x


def relu_masks(params, images, plans, order):
    """Sign pattern of every layer's pre-activation on the plan path."""
    z, masks = nhwc_to_plan(images), {}
    for name in order:
        pre = apply_conv(z, params[name], plans[name])
        masks[name] = pre > 0
        z = jax.nn.relu(pre)
    return masks


def ref_loss(params, batch, scenes, masks=None):
    z = ref_chain(nhwc_to_plan(batch["images"]), params, scenes, masks)
    logits = z.mean(axis=(0, 1)).T @ params["head"]
    return tc.softmax_cross_entropy(logits, batch["labels"])


# --------------------------------------------------------------------------
# one chip: serve
# --------------------------------------------------------------------------
def serve_phase(args) -> None:
    scenes = trunk(1, args.cpu_rehearsal)
    first = next(iter(scenes.values()))
    flts = seeded_weights(scenes, seed=args.seed)
    records = []
    sched = ConvScheduler(max_batch=MAX_BATCH, strict=True,
                          config=SchedConfig(max_gather_s=GATHER_S),
                          on_dispatch=records.append)
    sched.register_net("resnet", scenes, flts, activation=jax.nn.relu)
    t0 = time.perf_counter()
    with CompileCounter() as cc:
        built = sched.prewarm(compile=True)
    print(f"serve: prewarm built {built} plans, {cc.n} compiles, "
          f"{time.perf_counter() - t0:.3f} s")
    plans = sorted(sched.registry.plans().values(),
                   key=lambda p: (list(scenes).index(_layer_of(p, scenes)),
                                  p.scene.B))
    for p in plans:
        print(f"serve plan {_layer_of(p, scenes)} {p.op.value} "
              f"B={p.scene.B} {blocking(p)}")
    n_ref = sum(p.uses_reference for p in plans)
    print(f"serve: reference plans {n_ref}")
    check(n_ref == 0, f"{n_ref} serving plans fall back to the jnp reference")

    key = jax.random.PRNGKey(args.seed + 1000)
    images = jax.random.normal(
        key, (2, SINGLES + BURST) + first.in_shape()[:3], jnp.float32)
    sess = sched.session("resnet")

    def run_pattern(xs):
        outs = []
        for x in xs[:SINGLES]:                  # one at a time: bucket 1
            outs += sched.wait([sess.submit(x)])
        burst = [sess.submit(x[..., None]) for x in xs[SINGLES:]]
        outs += [o[..., 0] for o in sched.wait(burst)]
        return jax.block_until_ready(outs)

    sched.start()
    try:
        # The warm-up pass sends the exact pattern of the measured pass, so
        # the glue around the plans (concat, relu, lane slicing) compiles
        # here and the measured window must compile nothing.
        with CompileCounter() as cc_warm:
            run_pattern(images[0])
        n_warm = len(records)
        t0 = time.perf_counter()
        with CompileCounter() as cc:
            outs = run_pattern(images[1])
        steady_s = time.perf_counter() - t0
    finally:
        sched.stop()
    buckets = [r.bucket for r in records[n_warm:]]
    print(f"serve: warm-up pass {cc_warm.n} compiles; steady pass "
          f"{len(outs)} requests in {steady_s:.3f} s, dispatch buckets "
          f"{buckets}, {cc.n} compiles")
    check(cc.n == 0, f"{cc.n} compiles inside the steady-state requests")
    check(len(set(buckets)) >= 2,
          f"expected singles and a coalesced burst in different buckets, "
          f"got {buckets}")
    check(sched.stats()["plan_builds"] == 0, "a plan was built after prewarm")

    ref_fn = jax.jit(lambda x, w: ref_chain(x, w, scenes))
    worst = 0.0
    for i, (x, out) in enumerate(zip(images[1], outs)):
        want = ref_fn(x[..., None], flts)[..., 0]
        check(out.shape == want.shape,
              f"request {i} output {out.shape} != reference {want.shape}")
        err = rel_err(out, want)
        worst = max(worst, err)
        print(f"serve: request {i} out {tuple(out.shape)} "
              f"rel_err {err:.3e} (tol {SERVE_TOL:g})")
        check(bool(np.isfinite(np.asarray(out)).all()),
              f"request {i} output is not finite")
        check(err <= SERVE_TOL, f"request {i} rel_err {err:.3e} > "
                                f"{SERVE_TOL:g}")
    print(f"serve: ok, worst rel_err {worst:.3e}")


def _layer_of(plan, scenes) -> str:
    base = plan.scene.with_batch(1)
    return next(n for n, sc in scenes.items() if sc.with_batch(1) == base)


# --------------------------------------------------------------------------
# one chip: train
# --------------------------------------------------------------------------
def _batches(scenes, seed: int, n: int):
    first = next(iter(scenes.values()))
    data = SyntheticImages(first.B, first.inH, first.IC, N_CLASSES,
                           seed=seed)
    return [jax.tree.map(jnp.asarray, data.batch_at(i)) for i in range(n)]


def _grads(loss, params, batch):
    """Gradients at f32 matmul precision, so the head and the reference
    convolutions are exact in f32 (the Pallas kernels set theirs)."""
    with jax.default_matmul_precision("highest"):
        return jax.block_until_ready(jax.jit(jax.grad(loss))(params, batch))


def _grad_errs(got, want) -> dict:
    return {k: rel_err(got[k], want[k]) for k in want}


def train_phase(args) -> None:
    scenes = trunk(TRAIN_BATCH, args.cpu_rehearsal)
    params = init_cnn_from_scenes(jax.random.PRNGKey(args.seed), scenes,
                                  n_classes=N_CLASSES)
    plans = make_model_plans(scenes, policy="analytic")
    for layer, op, plan in plans.plans():
        print(f"train plan {layer} {op} {blocking(plan)}")
    n_ref = sum(len(ops) for ops in plans.reference_ops.values())
    print(f"train: reference plans {n_ref}")
    check(n_ref == 0, f"train plans fall back to the jnp reference: "
                      f"{plans.reference_ops}")
    order = plans.names()
    batches = _batches(scenes, args.seed, 3)

    # step 0's gradients, before the jitted step donates the parameters
    graph = chain_graph(order)
    g_plan = _grads(lambda p, b: tc.cnn_loss_fn(p, b, plans, graph)[0],
                    params, batches[0])
    masks = jax.jit(lambda p, x: relu_masks(p, x, plans, order))(
        params, batches[0]["images"])
    g_ref = _grads(lambda p, b: ref_loss(p, b, scenes, masks), params,
                   batches[0])
    errs = _grad_errs(g_plan, g_ref)
    for k, e in errs.items():
        print(f"train: step-0 grad {k} rel_err {e:.3e} (tol {GRAD_TOL:g})")
    check(max(errs.values()) <= GRAD_TOL,
          f"step-0 grads differ from the reference: {errs}")

    step = tc.jit_train_step(tc.build_cnn_train_step(
        plans, AdamWConfig(lr=1e-4, warmup_steps=1, total_steps=3),
        graph=graph))
    state = tc.init_train_state(params)
    # All three steps run with no schedule resolution (plan-once); step 0
    # compiles the step, steps 1-2 are the steady state.
    with tc.resolution_guard():
        t0 = time.perf_counter()
        with CompileCounter() as cc:
            state, metrics = step(state, batches[0])
            losses = [float(metrics["loss"])]
        print(f"train: step 0 (compile + run) "
              f"{time.perf_counter() - t0:.3f} s, {cc.n} compiles")
        t0 = time.perf_counter()
        with CompileCounter() as cc:
            for b in batches[1:]:
                state, metrics = step(state, b)
                losses.append(float(metrics["loss"]))
        print(f"train: steps 1-2 {time.perf_counter() - t0:.3f} s, "
              f"{cc.n} compiles, losses {losses}")
    check(cc.n == 0, f"{cc.n} compiles inside the steady-state steps")
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    print("train: ok")


# --------------------------------------------------------------------------
# four chips: sharded plans and one sharded training step
# --------------------------------------------------------------------------
def four_chip_phase(args) -> None:
    check(jax.device_count() == 4,
          f"--four-chips needs 4 devices, found {jax.device_count()}")
    ring = data_devices(make_mesh_for(4, 1))
    print(f"shard: ring devices {[d.id for d in ring]}")
    check(len({d.id for d in ring}) == 4, "the mesh ring repeats a device")
    scenes = trunk(TRAIN_BATCH, args.cpu_rehearsal)
    flts = seeded_weights(scenes, seed=args.seed)
    key = jax.random.PRNGKey(args.seed + 2000)
    axes_run = set()
    for name, sc in scenes.items():
        inp = jax.random.normal(key, sc.in_shape(), jnp.float32)
        want = make_plan(sc).execute(inp, flts[name])
        for axis in PARTITION_AXES:
            why = shard_blocker(sc, axis, 4)
            if why:
                print(f"shard: {name} {axis}:4 skipped ({why})")
                continue
            choice = select_schedule(shard_sub_scene(sc, axis, 4))
            plan = make_sharded_plan(
                sc, ConvOp.FPROP, devices=ring,
                spec=pinned_shard_spec(sc, ConvOp.FPROP, axis, 4, choice))
            got = jax.block_until_ready(plan.execute(inp, flts[name]))
            holders = sorted({s.device.id for s in got.addressable_shards})
            err = rel_err(got, want)
            print(f"shard: {name} {plan.shard_tag} {choice.schedule}"
                  f"({choice.bm}/{choice.bn}/{choice.bk}) rel_err {err:.3e} "
                  f"(tol {SHARD_TOL:g}) output on devices {holders} "
                  f"spec {got.sharding.spec if hasattr(got.sharding, 'spec') else got.sharding}")
            check(err <= SHARD_TOL, f"{name} {plan.shard_tag} rel_err "
                                    f"{err:.3e} > {SHARD_TOL:g}")
            check(len(holders) == 4,
                  f"{name} {plan.shard_tag} output held by {holders}")
            axes_run.add(axis)
    check(axes_run == set(PARTITION_AXES),
          f"partition axes exercised: {sorted(axes_run)}")

    params = init_cnn_from_scenes(jax.random.PRNGKey(args.seed), scenes,
                                  n_classes=N_CLASSES)
    one = make_model_plans(scenes, policy="analytic")
    sharded = make_model_plans(scenes, policy="analytic", devices=ring)
    for layer in sharded:
        print(f"shard: train {layer} tags {sharded[layer].shard_tags}")
    n_sharded = sum(t != "-" and not t.endswith(":1")
                    for layer in sharded for t in sharded[layer].shard_tags)
    batches = _batches(scenes, args.seed, 1)
    graph = chain_graph(one.names())
    g_one = _grads(lambda p, b: tc.cnn_loss_fn(p, b, one, graph)[0],
                   params, batches[0])
    g_sh = _grads(lambda p, b: tc.cnn_loss_fn(p, b, sharded, graph)[0],
                  params, batches[0])
    errs = _grad_errs(g_sh, g_one)
    print(f"shard: {n_sharded} directions sharded; grads vs one-chip plans "
          f"worst rel_err {max(errs.values()):.3e} (tol {SHARD_TOL:g})")
    check(max(errs.values()) <= SHARD_TOL,
          f"sharded grads differ from the one-chip plans: {errs}")
    opt_cfg = AdamWConfig(lr=1e-4, warmup_steps=1, total_steps=1)
    losses = []
    for p in (one, sharded):
        step = tc.jit_train_step(tc.build_cnn_train_step(
            p, opt_cfg, graph=graph))
        _, metrics = step(tc.init_train_state(
            jax.tree.map(jnp.copy, params)), batches[0])
        losses.append(float(metrics["loss"]))
    print(f"shard: one step loss one-chip {losses[0]!r} sharded "
          f"{losses[1]!r}")
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(abs(losses[1] - losses[0]) <= SHARD_TOL * max(abs(losses[0]), 1.0),
          f"sharded step loss {losses[1]} != one-chip {losses[0]}")
    print("shard: ok")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path on four chips")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="shrunken trunk in the Pallas interpreter; never "
                         "reports ok")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.cpu_rehearsal:
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"use --cpu-rehearsal for a run without the chip",
              file=sys.stderr)
        return 2
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count()}
    print(f"device: {device}")
    print(f"compile cache: {enable_compile_cache()}")
    cm = active_cost_model()
    print(f"cost model: {cm.source} calibrated={cm.is_calibrated}")
    try:
        check(not cm.is_calibrated,
              f"a calibration artifact ({cm.source}) would steer selection; "
              f"schedules must come from the committed analytic model")
        if args.four_chips:
            four_chip_phase(args)
        else:
            serve_phase(args)
            train_phase(args)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    if args.cpu_rehearsal:
        print(json.dumps({"rehearsal": "passed", "device": device}))
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
