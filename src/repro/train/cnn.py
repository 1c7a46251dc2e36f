"""Plan-driven CNN training — every fprop/dgrad/wgrad is a prewarmed ConvPlan.

The missing half of the plan architecture: PRs 1-8 built tuning,
calibration, plan caching, sharding and drift monitoring, but the training
substrate predated all of it — no CNN training step ever touched a
``ConvPlan``.  This module closes the loop:

  * ``build_cnn_train_step``: one jittable ``(TrainState, batch) ->
    (TrainState, metrics)`` over a ``ModelPlans`` — forward through
    ``models.cnn.cnn_forward_planned`` over a layer graph (activations stay
    in plan layout across the stack), backward through each layer's prewarmed
    dgrad/wgrad plans via the ``conv_with_plans`` custom_vjp, update via
    the existing pytree-agnostic ``optimizer.adamw_update``.  Microbatch
    gradient accumulation reuses the ``lax.scan`` shape of
    ``train/step.py``; with ``GradBuckets`` the scan carry is a handful of
    flat f32 buffers instead of one accumulator per parameter, so the
    cross-device gradient reduction (``grad_reduce``) runs as a few large
    collectives — flat-buffer bucketing in the spirit of apex's fused
    distributed optimizers.
  * ``build_cnn_train_loop``: K steps fused under one
    ``lax.scan(step, state, data, unroll=2)`` with the ``TrainState``
    carry donated — the olmax train-loop shape — so steady state is one
    dispatch per K steps.
  * host-side instrumentation: ``dispatch_step`` enqueues a step under a
    ``repro.train.step`` span; ``observe_step`` / ``observe_plan_hit_rate``
    / ``profile_step_breakdown`` record the ``repro.train.*`` metrics, and
    ``feed_drift_from_plans`` streams each plan's (predicted, measured)
    dispatch seconds into the cost-model drift monitor, extending the
    always-on calibration audit from tuning/serving to training.

Zero steady-state resolutions is the contract, not an aspiration:
``resolution_guard`` snapshots the ``repro.plan.resolutions`` counter
after warmup and raises if any later step resolved a schedule.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.models.cnn import cnn_forward_planned
from repro.obs.metrics import MetricRegistry, default_metrics
from repro.obs.trace import default_tracer
from repro.train import optimizer as opt
from repro.train.optimizer import TrainState

F32 = jnp.float32


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean CE of integer labels — mask+sum instead of take_along_axis (the
    same class-parallel-safe shape ``step.cross_entropy`` uses)."""
    logits = logits.astype(F32)
    lse = jax.nn.logsumexp(logits, -1)
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    picked = jnp.sum(jnp.where(iota == labels[..., None], logits, 0.0), -1)
    return (lse - picked).mean()


def cnn_loss_fn(params, batch: Dict[str, jax.Array], plans,
                graph: Optional[Sequence] = None) -> Tuple[jax.Array, Dict]:
    """CE loss of the plan-layout forward over the layer ``graph`` (the
    relu chain of the plans' layers when None); batch = {"images" NHWC,
    "labels" int}.  ``plans`` is nondiff (closed over / static)."""
    logits = cnn_forward_planned(params, batch["images"], plans, graph=graph)
    loss = softmax_cross_entropy(logits, batch["labels"])
    acc = (logits.argmax(-1) == batch["labels"]).mean()
    return loss, {"accuracy": acc}


# ---------------------------------------------------------------------------
# flat-buffer gradient bucketing
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class GradBuckets:
    """Greedy size-capped packing of the parameter leaves into contiguous
    f32 buffers.

    ``flatten`` ravels a gradient tree into ``n_buckets`` 1-D buffers;
    ``unflatten`` inverts it.  Accumulating and reducing in this form
    turns per-leaf adds and collectives into a few large contiguous ones
    (apex ``distributed_fused_adam`` flat-buffer spirit) — the microbatch
    scan in ``build_cnn_train_step`` carries exactly these buffers.
    Frozen/hashable so step functions can close over it under jit.
    """

    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    sizes: Tuple[int, ...]
    edges: Tuple[int, ...]      # leaf-index boundaries; bucket b covers
                                # leaves[edges[b] : edges[b + 1]]

    @property
    def n_buckets(self) -> int:
        return len(self.edges) - 1

    def zeros(self) -> Tuple[jax.Array, ...]:
        """Zeroed accumulator buffers (the scan carry's initial value)."""
        return tuple(
            jnp.zeros(sum(self.sizes[self.edges[b]:self.edges[b + 1]]), F32)
            for b in range(self.n_buckets))

    def flatten(self, grads) -> Tuple[jax.Array, ...]:
        leaves = self.treedef.flatten_up_to(grads)
        bufs = []
        for b in range(self.n_buckets):
            lo, hi = self.edges[b], self.edges[b + 1]
            bufs.append(jnp.concatenate(
                [leaves[i].astype(F32).ravel() for i in range(lo, hi)]))
        return tuple(bufs)

    def unflatten(self, bufs: Sequence[jax.Array]):
        leaves = []
        for b in range(self.n_buckets):
            off = 0
            for i in range(self.edges[b], self.edges[b + 1]):
                n = self.sizes[i]
                leaves.append(bufs[b][off:off + n].reshape(self.shapes[i]))
                off += n
        return self.treedef.unflatten(leaves)


def make_grad_buckets(params, *, bucket_mb: float = 4.0) -> GradBuckets:
    """Pack the parameter tree's leaves, in tree order, into buckets of at
    most ``bucket_mb`` MiB of f32 gradient each (a leaf larger than the cap
    gets its own bucket)."""
    if bucket_mb <= 0:
        raise ValueError(f"bucket_mb must be positive, got {bucket_mb}")
    leaves, treedef = jax.tree.flatten(params)
    shapes = tuple(tuple(x.shape) for x in leaves)
    sizes = tuple(int(x.size) for x in leaves)
    cap = int(bucket_mb * 2 ** 20 / 4)          # f32 elements per bucket
    edges = [0]
    filled = 0
    for i, n in enumerate(sizes):
        if filled and filled + n > cap:
            edges.append(i)
            filled = 0
        filled += n
    edges.append(len(sizes))
    return GradBuckets(treedef=treedef, shapes=shapes, sizes=sizes,
                       edges=tuple(edges))


# ---------------------------------------------------------------------------
# step / loop builders
# ---------------------------------------------------------------------------
def build_cnn_train_step(plans, opt_cfg: opt.AdamWConfig, *,
                         n_microbatches: int = 1,
                         buckets: Optional[GradBuckets] = None,
                         grad_reduce: Optional[Callable] = None,
                         graph: Optional[Sequence] = None,
                         loss_fn: Optional[Callable] = None,
                         with_grads: bool = False):
    """Build ``train_step(state, batch) -> (state, metrics)`` over a
    ``ModelPlans``; the forward walks ``graph`` (``models.cnn`` layer
    graph; the relu chain of the plans' layers when None).  ``with_grads``
    also returns the (reduced, unclipped) gradients the update applied as
    ``metrics["grads"]``.

    Plans are fixed-geometry: build ``plans`` for the *microbatch* size
    (``global_batch // n_microbatches``) — the forward only ever sees one
    microbatch.  Gradients accumulate over ``n_microbatches`` slices of
    the batch under ``lax.scan`` (the ``train/step.py`` accumulation
    shape).  With
    ``buckets`` the carry is the flat buffers; ``grad_reduce`` (e.g. a
    ``psum`` over the data axis, or a mean across replicas) then runs once
    per bucket — a few large contiguous collectives overlapping nothing
    per-leaf.  Jit the result via ``jit_train_step`` (donated state) or
    fuse K steps via ``build_cnn_train_loop``.
    """
    if n_microbatches < 1:
        raise ValueError(
            f"n_microbatches must be >= 1, got {n_microbatches}")
    lfn = loss_fn if loss_fn is not None else functools.partial(
        cnn_loss_fn, plans=plans, graph=graph)

    def one_microbatch(params, mb):
        (loss, stats), grads = jax.value_and_grad(
            lfn, has_aux=True)(params, mb)
        return loss, stats, grads

    def train_step(state: TrainState, batch):
        n_mb = n_microbatches
        if (loss_fn is None and hasattr(plans, "scenes")
                and isinstance(batch, dict) and "images" in batch):
            plan_b = next(iter(plans.scenes().values())).B
            if batch["images"].shape[0] != plan_b * n_mb:
                raise ValueError(
                    f"batch of {batch['images'].shape[0]} images does not "
                    f"match plans built for microbatch B={plan_b} x "
                    f"{n_mb} microbatches — build the plans for the "
                    f"microbatch size (global_batch // n_microbatches)")
        if n_mb == 1:
            loss, stats, grads = one_microbatch(state.params, batch)
            bufs = buckets.flatten(grads) if buckets is not None else None
        else:
            def reshape_mb(x):
                return x.reshape(n_mb, x.shape[0] // n_mb, *x.shape[1:])
            mbs = jax.tree.map(reshape_mb, batch)
            if buckets is not None:
                # flat-buffer accumulation: the carry is n_buckets
                # contiguous f32 buffers, not one accumulator per leaf
                def acc_body(carry, mb):
                    acc, l_acc = carry
                    loss, stats, grads = one_microbatch(state.params, mb)
                    acc = tuple(a + g for a, g in
                                zip(acc, buckets.flatten(grads)))
                    return (acc, l_acc + loss), stats

                (bufs, l_acc), stats = jax.lax.scan(
                    acc_body, (buckets.zeros(), 0.0), mbs)
                bufs = tuple(b / n_mb for b in bufs)
                grads = None
            else:
                def acc_body(carry, mb):
                    g_acc, l_acc = carry
                    loss, stats, grads = one_microbatch(state.params, mb)
                    g_acc = jax.tree.map(
                        lambda a, g: a + g.astype(F32), g_acc, grads)
                    return (g_acc, l_acc + loss), stats

                g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, F32),
                                  state.params)
                (g_acc, l_acc), stats = jax.lax.scan(acc_body, (g0, 0.0),
                                                     mbs)
                grads = jax.tree.map(lambda g: g / n_mb, g_acc)
                bufs = None
            loss = l_acc / n_mb
            stats = jax.tree.map(lambda s: s.mean(), stats)
        if bufs is not None:
            if grad_reduce is not None:
                bufs = tuple(grad_reduce(b) for b in bufs)
            grads = buckets.unflatten(bufs)
        elif grad_reduce is not None:
            grads = jax.tree.map(grad_reduce, grads)
        new_params, new_opt, om = opt.adamw_update(
            opt_cfg, state.params, grads, state.opt)
        metrics = dict(om, loss=loss, **stats)
        if with_grads:
            metrics["grads"] = grads
        return TrainState(new_params, new_opt), metrics

    return train_step


def jit_train_step(step_fn):
    """One-step jit with the ``TrainState`` buffers donated — params and
    moments update in place instead of doubling live memory."""
    return jax.jit(step_fn, donate_argnums=(0,))


def dispatch_step(jstep, state: TrainState, batch):
    """Enqueue one jitted (or compiled) step under a ``repro.train.step``
    span and return its outputs without waiting for them."""
    with default_tracer().span("repro.train.step"):
        return jstep(state, batch)


def build_cnn_train_loop(step_fn, *, unroll: int = 2):
    """Fuse K steps into one dispatch: ``lax.scan(step, state, data,
    unroll=2)`` over stacked batches (leaves ``[K, ...]``), state donated —
    the olmax train-loop shape.  Returns jitted
    ``train_loop(state, data) -> (state, stacked_metrics)``."""
    def train_loop(state: TrainState, data):
        return jax.lax.scan(step_fn, state, data, unroll=unroll)

    return jax.jit(train_loop, donate_argnums=(0,))


def init_train_state(params, *, moments_dtype: str = "float32") -> TrainState:
    return TrainState(params=params,
                      opt=opt.init_opt_state(params,
                                             moments_dtype=moments_dtype))


# ---------------------------------------------------------------------------
# instrumentation (host side — record around the jitted dispatches)
# ---------------------------------------------------------------------------
def observe_step(seconds: float, loss: float, n_examples: int,
                 metrics: Optional[MetricRegistry] = None) -> None:
    """Record one optimizer step into the ``repro.train.*`` metrics."""
    m = metrics if metrics is not None else default_metrics()
    m.histogram("repro.train.step_s").observe(seconds)
    m.counter("repro.train.steps").inc()
    m.counter("repro.train.examples").inc(n_examples)
    m.gauge("repro.train.loss").set(float(loss))


def observe_plan_hit_rate(registry=None,
                          metrics: Optional[MetricRegistry] = None) -> float:
    """Record the plan registry's lifetime hit rate as
    ``repro.train.plan_hit_rate`` (1.0 = every training dispatch after
    prewarm was a pure cache hit) and return it."""
    from repro.plan.registry import default_registry
    reg = registry if registry is not None else default_registry()
    rate = reg.stats()["hit_rate"]
    m = metrics if metrics is not None else default_metrics()
    m.gauge("repro.train.plan_hit_rate").set(rate)
    return rate


def profile_step_breakdown(state: TrainState, batch, plans,
                           opt_cfg: opt.AdamWConfig, *,
                           graph: Optional[Sequence] = None,
                           metrics: Optional[MetricRegistry] = None
                           ) -> Dict[str, float]:
    """Time the two halves the fused step welds together — value_and_grad
    (forward + both backward plan walks) and the AdamW update — and record
    them as ``repro.train.grads_s`` / ``repro.train.update_s``.  Run once
    after warmup; the fused step itself cannot be split from outside jit.
    """
    m = metrics if metrics is not None else default_metrics()
    lfn = functools.partial(cnn_loss_fn, plans=plans, graph=graph)
    grads_fn = jax.jit(lambda p, b: jax.value_and_grad(
        lfn, has_aux=True)(p, b))
    (_, _), grads = grads_fn(state.params, batch)          # compile
    jax.block_until_ready(grads)
    t0 = time.perf_counter()
    (_, _), grads = grads_fn(state.params, batch)
    jax.block_until_ready(grads)
    grads_s = time.perf_counter() - t0

    upd_fn = jax.jit(lambda p, g, s: opt.adamw_update(opt_cfg, p, g, s))
    jax.block_until_ready(upd_fn(state.params, grads, state.opt))  # compile
    t0 = time.perf_counter()
    jax.block_until_ready(upd_fn(state.params, grads, state.opt))
    update_s = time.perf_counter() - t0

    m.histogram("repro.train.grads_s").observe(grads_s)
    m.histogram("repro.train.update_s").observe(update_s)
    return {"grads_s": grads_s, "update_s": update_s}


def feed_drift_from_plans(plans, monitor=None) -> int:
    """Stream a timed dispatch of every non-reference plan in a
    ``ModelPlans`` into the cost-model drift monitor — the training-side
    leg of the always-on calibration audit (tuning and serving already
    feed it).  Returns the number of (predicted, measured) pairs observed.
    """
    from repro.obs.drift import default_monitor, scene_class
    mon = monitor if monitor is not None else default_monitor()
    fed = 0
    for _layer, _opname, plan in plans.plans():
        if plan.uses_reference or plan.choice is None:
            continue
        a_shape, b_shape, _ = plan.io_shapes()
        a = jnp.zeros(a_shape, plan.scene.dtype)
        b = jnp.zeros(b_shape, plan.scene.dtype)
        jax.block_until_ready(plan.execute(a, b))          # compile/warm
        t0 = time.perf_counter()
        jax.block_until_ready(plan.execute(a, b))
        measured = time.perf_counter() - t0
        mon.observe(scene_class(plan.exec_scene, plan.choice),
                    plan.predicted_s, measured)
        fed += 1
    return fed


class resolution_guard:
    """Context manager asserting the plan-once contract: zero schedule
    resolutions inside the guarded region.  Enter after warmup, wrap the
    steady-state steps; raises ``ValueError`` naming the count otherwise.

        with resolution_guard():
            for _ in range(n_steps):
                state, ms = jstep(state, batch)
    """

    def __init__(self, metrics: Optional[MetricRegistry] = None):
        self._m = metrics if metrics is not None else default_metrics()
        self._before = 0.0

    def __enter__(self) -> "resolution_guard":
        self._before = self._m.value("repro.plan.resolutions")
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is None:
            after = self._m.value("repro.plan.resolutions")
            if after > self._before:
                raise ValueError(
                    f"plan-once contract violated: "
                    f"{int(after - self._before)} schedule resolution(s) "
                    f"occurred inside a resolution_guard (expected zero "
                    f"after warmup)")
        return False
