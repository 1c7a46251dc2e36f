"""Scene-bucketed micro-batching conv serving engine with plan prewarming.

The paper's claim is *adaptability across convolution scenes*; a serving
process meets traffic that varies only along one axis the selector already
understands — batch.  ``ConvServer`` turns that into the execution shape the
multi-grained selector scores best:

  * each registered layer defines a scene *family* (``ConvScene.family_key``,
    B-agnostic); concurrent requests against one layer differ only in batch
    size, so they coalesce along the B axis (the MM_unit N dim — independent
    GEMM columns, bitwise-safe to pack and slice) into one batched
    ``ConvPlan.execute``;
  * coalesced batches pad up to a **bucket ladder** of batch sizes chosen
    per scene family from the ``CostModel``: a ladder rung is dropped when
    the model predicts the next rung costs no more to run
    (``predicted_s`` within ``ladder_slack``), i.e. the rung sits below the
    chosen schedule's granularity sweet spot and the MXU would burn the
    lane-quantized work anyway — padding up is free, and fewer buckets mean
    fewer plans and fatter batches;
  * at startup the server prewarms every (layer x op x bucket) plan into a
    thread-safe ``PlanRegistry`` (``PlanRegistry.warm``) from a model's
    scene list (``models.cnn.cnn_layer_scenes``) or a saved registry
    artifact, so steady-state serving is pure kernel dispatch: zero plan
    builds, zero schedule resolutions (``stats()['plan_misses']`` stays 0,
    assertable; ``on_dispatch`` is the audit hook).

Padding lanes are zeros: a zero batch column produces a zero output column
for FPROP/DGRAD (both are linear in the batched operand), sliced off before
the request completes, so coalesced output matches per-request execution.
WGRAD *contracts over* B — batching requests along B would sum their
gradients — so the server refuses it; use ``ConvPlan`` directly.

``mesh=`` extends the same argument one level up: a coalesced bucket's B
axis is exactly the independent-GEMM-column axis the mesh's data dimension
partitions, so in mesh mode every (layer x op x bucket) prewarms a
``ShardedConvPlan`` (``repro.shard``, ``axes=("batch",)``) across the
mesh's data-axis device ring instead of a single-device plan.  The joint
selector still owns the decision — a bucket too small to amortize the
shard_map launch falls back to ``n_shards == 1`` — and the chosen partition
tag per (layer, op, bucket) is recorded at prewarm, so steady state stays
a zero-resolution registry lookup (tag dict hit + shard-keyed ``get``).

Observability: every server owns a ``MetricRegistry`` (``repro.serve.*``
counters + queue-wait/enqueue histograms; ``stats(since=snapshot())``
windows them).  Each stage a request passes through runs under a span —
``repro.serve.submit``, ``repro.serve.step``, ``repro.serve.dispatch``,
``repro.serve.glue`` (concat/pad before the plan, lane slices after; one
compiled call each, whatever the group size),
``repro.plan.execute`` and, at start-up, ``repro.serve.prewarm`` — which
becomes a host event of a recording ``jax.profiler`` session and, with the
tracer enabled, a buffered Chrome-trace event (``repro.obs.trace``).
Tracing never waits on the device: a dispatch is asynchronous either way,
``repro.serve.enqueue_s`` times its host side (outputs not ready), and the
profiler's device plane, not a span, says when the kernels ran.
``DispatchRecord``s are published directly to ``on_dispatch`` after every
successful dispatch; a hook that raises is counted
(``repro.serve.dispatch_hook_errors``) and never fails the dispatch.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import os
import threading
import time
from typing import Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.mapping import (CostModel, predicted_efficiency,
                                select_schedule)
from repro.core.scene import ConvScene
from repro.obs.metrics import (DEFAULT_RATIO_BUCKETS, MetricRegistry,
                               snapshot_delta, snapshot_value)
from repro.obs.trace import Tracer, default_tracer
from repro.plan import ConvOp, ConvPlan, PlanRegistry, make_plan
from repro.plan.build import PolicySpec, _active_cost_model


# --------------------------------------------------------------------------
# bucket ladder — batch buckets per scene family, chosen by the cost model
# --------------------------------------------------------------------------
def bucket_ladder(scene: ConvScene, max_batch: int, *, min_bucket: int = 1,
                  slack: float = 1.15,
                  model: Optional[CostModel] = None) -> Tuple[int, ...]:
    """Batch buckets for one scene family: power-of-two rungs from
    ``min_bucket`` up, capped by ``max_batch`` (always the top rung), pruned
    bottom-up by the cost model.

    A rung ``b`` is dropped when the model predicts the next *surviving*
    rung runs within ``slack`` of it
    (``predicted_s(next_kept) <= slack * predicted_s(b)``): below the
    selected schedule's granularity sweet spot the MXU's lane/sublane
    quantization burns the bigger batch's work anyway (a compute-bound
    scene costs the same at B=8 and B=64), so padding those requests up to
    the rung they will actually execute at is ~free and the ladder should
    not hold a plan below it.  The comparison is deliberately against the
    kept rung, not the adjacent one — pairwise-adjacent pruning would let
    sub-``slack`` ratios compound (seven 1.12x steps ≈ 2.2x) and collapse
    ladders whose cumulative padding cost is far from free.  Memory-bound
    families, whose time scales with B, keep the full ladder.
    ``model=None`` uses the active (calibrated when an artifact exists)
    cost model, like plan building does.
    """
    if max_batch < 1 or min_bucket < 1:
        raise ValueError("max_batch and min_bucket must be positive")
    if min_bucket > max_batch:
        raise ValueError(f"min_bucket {min_bucket} exceeds max_batch "
                         f"{max_batch}")
    model = model if model is not None else _active_cost_model()
    rungs = []
    b = min_bucket
    while b < max_batch:
        rungs.append(b)
        b *= 2
    rungs.append(max_batch)
    if slack <= 0:
        return tuple(rungs)   # pruning is provably a no-op: skip the
        # per-rung schedule resolutions entirely
    times = {b: select_schedule(scene.with_batch(b), model=model).predicted_s
             for b in rungs}
    # top-down: keep a rung iff padding it up to the lowest kept rung above
    # it is NOT within slack (the invariant holds against the bucket a
    # request would actually execute at, never a pruned intermediate)
    kept = [rungs[-1]]
    for b in reversed(rungs[:-1]):
        if times[kept[0]] > slack * times[b]:
            kept.insert(0, b)
    return tuple(kept)


# --------------------------------------------------------------------------
# lane glue — a group's gather before the plans and split after, each one
# compiled call whatever the group size
# --------------------------------------------------------------------------
def _needs_gather(group: Sequence["ConvRequest"], bucket: int) -> bool:
    return len(group) > 1 or group[0]._b < bucket


def _needs_split(group: Sequence["ConvRequest"], bucket: int) -> bool:
    return len(group) > 1 or group[0]._squeeze or group[0]._b < bucket


@functools.partial(jax.jit, static_argnames=("bucket",))
def _gather_lanes(xs: Tuple[jax.Array, ...], bucket: int) -> jax.Array:
    """``xs`` concatenated along B and zero-padded to ``bucket`` lanes."""
    x = jnp.concatenate(xs, axis=3)
    return jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, bucket - x.shape[3])))


@functools.partial(jax.jit, static_argnames=("widths", "squeeze"))
def _split_lanes(out: jax.Array, widths: Tuple[int, ...],
                 squeeze: Tuple[bool, ...]) -> Tuple[jax.Array, ...]:
    """Consecutive lane slices of ``out``, ``widths[i]`` lanes each, the
    batch axis dropped where ``squeeze[i]``."""
    outs, off = [], 0
    for b, sq in zip(widths, squeeze):
        sl = out[..., off:off + b]
        outs.append(sl[..., 0] if sq else sl)
        off += b
    return tuple(outs)


# --------------------------------------------------------------------------
# requests and dispatch records
# --------------------------------------------------------------------------
@dataclasses.dataclass(eq=False)
class ConvRequest:
    """One unit of per-request conv work: an input tensor against a
    registered layer.  ``x`` is in the paper layout with a trailing batch
    axis — ``[inH, inW, IC, b]`` for FPROP, ``[outH, outW, OC, b]`` for
    DGRAD — or 3-D (no batch axis) meaning ``b = 1``, in which case the
    result comes back 3-D too.  ``out``, ``done``, and (on a failed
    dispatch) ``error`` are filled by the server on completion.

    ``deadline_s`` is an optional latency budget in seconds, relative to
    submission.  The base ``ConvServer`` dispatches on demand and merely
    records it; the scheduling layer (``repro.serve.sched``) uses it to
    flush partial buckets before the budget expires and to order the queue
    under overload (EDF shed policy).

    ``eq=False``: requests are identity objects.  A value ``__eq__`` would
    compare the jax arrays (ambiguous truth value) and would let two
    requests with equal fields alias each other in the queue."""

    rid: int
    layer: str
    x: jax.Array
    op: ConvOp = ConvOp.FPROP
    deadline_s: Optional[float] = None
    out: Optional[jax.Array] = None
    done: bool = False
    error: Optional[BaseException] = None
    # internal: batch width, whether to squeeze the result (3-D input),
    # submission timestamp (queue-wait metric), the absolute deadline
    # (perf_counter clock, derived from deadline_s at submit), and the
    # completion signal serve() waits on (set by whichever thread's step()
    # dispatches the batch containing this request)
    _b: int = dataclasses.field(default=0, repr=False)
    _squeeze: bool = dataclasses.field(default=False, repr=False)
    _t_submit: float = dataclasses.field(default=0.0, repr=False)
    _t_deadline: Optional[float] = dataclasses.field(default=None, repr=False)
    _event: Optional[threading.Event] = dataclasses.field(default=None,
                                                          repr=False)


@dataclasses.dataclass(frozen=True)
class DispatchRecord:
    """One coalesced kernel dispatch — the audit unit of the serving layer
    (``on_dispatch`` receives these)."""

    layer: str
    op: ConvOp
    bucket: int        # padded batch the plan executed
    occupied: int      # real request lanes in the bucket
    requests: int      # how many requests were coalesced
    schedule: Optional[str]


@dataclasses.dataclass(frozen=True)
class _Family:
    """One registered layer: its B-agnostic scene family, weight, ladder."""

    layer: str
    base: ConvScene               # canonical B=1 member of the family
    flt: jax.Array
    ops: Tuple[ConvOp, ...]
    ladder: Tuple[int, ...]

    def a_spatial(self, op: ConvOp) -> Tuple[int, int, int]:
        """Expected leading (non-batch) dims of a request tensor."""
        if op is ConvOp.FPROP:
            return (self.base.inH, self.base.inW, self.base.IC)
        return (self.base.outH, self.base.outW, self.base.OC)


# --------------------------------------------------------------------------
# the server
# --------------------------------------------------------------------------
class ConvServer:
    """Scene-bucketed micro-batching conv server over a prewarmed
    ``PlanRegistry``.

    Lifecycle: ``register_layer`` every (scene, weight) the model serves,
    ``prewarm()`` once (optionally from a saved registry artifact), then
    ``submit``/``drain`` — or ``serve(requests)`` for both — from any number
    of threads.  ``step()`` coalesces the longest eligible run of queued
    requests for one (layer, op) along the B axis, pads to the family's
    bucket ladder, executes the prewarmed plan, and slices each request's
    lanes back out.

    ``strict=True`` turns any post-warm plan miss into a ``RuntimeError``
    (production posture: steady state must be pure dispatch); the default
    builds the missing plan and counts it in ``stats()['plan_builds']``.
    """

    def __init__(self, *, registry: Optional[PlanRegistry] = None,
                 policy: PolicySpec = "analytic", max_batch: int = 32,
                 min_bucket: int = 1, ladder_slack: float = 1.15,
                 cost_model: Optional[CostModel] = None, strict: bool = False,
                 on_dispatch: Optional[Callable[[DispatchRecord], None]]
                 = None, metrics: Optional[MetricRegistry] = None,
                 tracer: Optional[Tracer] = None, mesh=None):
        self.registry = registry if registry is not None else PlanRegistry()
        self.mesh = mesh
        if mesh is not None:
            from repro.launch.mesh import data_devices
            self._ring: Optional[Tuple] = data_devices(mesh)
        else:
            self._ring = None
        # mesh mode: chosen partition tag per (layer, op, bucket), recorded
        # at prewarm so steady state never re-runs the joint selector
        self._shard_tags: Dict[Tuple[str, ConvOp, int], str] = {}
        self.policy = policy
        self.max_batch = max_batch
        self.min_bucket = min_bucket
        self.ladder_slack = ladder_slack
        self.cost_model = cost_model
        self.strict = strict
        self.on_dispatch = on_dispatch
        self._lock = threading.RLock()
        self._layers: Dict[str, _Family] = {}
        self._queue: "collections.deque[ConvRequest]" = collections.deque()
        self._seq = itertools.count()
        self._warmed = False
        # serving metrics (post-warm steady state); per-instance registry so
        # two servers in one process never mix counters — pass ``metrics``
        # to aggregate several servers into one registry instead
        self.metrics = metrics if metrics is not None else MetricRegistry()
        self.tracer = tracer if tracer is not None else default_tracer()
        self._c_requests = self.metrics.counter("repro.serve.requests")
        self._c_dispatches = self.metrics.counter("repro.serve.dispatches")
        self._c_occupied = self.metrics.counter("repro.serve.occupied_lanes")
        self._c_bucket = self.metrics.counter("repro.serve.bucket_lanes")
        self._c_plan_misses = self.metrics.counter("repro.serve.plan_misses")
        self._c_plan_builds = self.metrics.counter("repro.serve.plan_builds")
        self._c_hook_errors = self.metrics.counter(
            "repro.serve.dispatch_hook_errors")
        self._g_queue = self.metrics.gauge("repro.serve.queue_depth")
        self._h_wait = self.metrics.histogram("repro.serve.queue_wait_s")
        # host seconds from dispatch start to the group's last enqueue; the
        # outputs are not ready then (dispatch is asynchronous)
        self._h_enqueue = self.metrics.histogram("repro.serve.enqueue_s")
        self._h_occupancy = self.metrics.histogram(
            "repro.serve.occupancy", bounds=DEFAULT_RATIO_BUCKETS)

    # -- setup -------------------------------------------------------------
    def register_layer(self, layer: str, scene: ConvScene, flt: jax.Array,
                       ops: Sequence[ConvOp] = (ConvOp.FPROP,)) -> _Family:
        """Register one servable layer: scene family + weight.  Layers whose
        scenes share a ``family_key`` automatically share ladder plans in
        the registry (identical rebatched scenes produce identical plan
        signatures) — weights stay per-layer, so only the *plans* dedup."""
        ops = tuple(ConvOp(op) for op in ops)
        if ConvOp.WGRAD in ops:
            raise ValueError(
                "wgrad contracts over the batch axis — coalescing requests "
                "along B would sum their gradients; serve wgrad through "
                "ConvPlan directly")
        if flt.shape != scene.flt_shape():
            raise ValueError(
                f"layer {layer!r} weight shape {flt.shape} does not match "
                f"the scene's FLT layout {scene.flt_shape()}")
        base = scene.with_batch(1)
        ladder = bucket_ladder(base, self.max_batch,
                               min_bucket=self.min_bucket,
                               slack=self.ladder_slack, model=self.cost_model)
        fam = _Family(layer=layer, base=base, flt=flt, ops=ops, ladder=ladder)
        with self._lock:
            if layer in self._layers:
                raise ValueError(f"layer {layer!r} already registered")
            self._layers[layer] = fam
            self._warmed = False
        return fam

    def prewarm(self, artifact: Optional[str] = None, *,
                compile: bool = False) -> int:
        """Build every (layer x op x bucket) plan the server can dispatch;
        returns how many plans were built (0 = everything was already
        pinned).  ``artifact`` loads a saved registry first, so a restarted
        server re-resolves nothing — loaded plans are pinned choices and
        ``warm`` only fills genuine gaps.  ``compile=True`` additionally
        executes each servable plan once on zeros, paying kernel JIT before
        traffic instead of inside the first request's latency."""
        if artifact and os.path.exists(artifact):
            self.registry.load(artifact)
        built = 0
        with self._lock:
            families = list(self._layers.values())
        for fam in families:
            with self._prewarm_span(fam, "plans"):
                if self._ring is not None:
                    built += self._prewarm_sharded(fam)
                else:
                    built += self.registry.warm(
                        [fam.base], ops=fam.ops, buckets=fam.ladder,
                        policy=self.policy)
        if compile:
            for fam in families:
                with self._prewarm_span(fam, "compile"):
                    self._compile(fam, fam.ladder)
        with self._lock:
            self._warmed = True
        return built

    def _prewarm_span(self, fam: _Family, stage: str):
        """The ``repro.serve.prewarm`` span of one family's start-up
        ``stage``."""
        return self.tracer.span("repro.serve.prewarm", layer=fam.layer,
                                stage=stage)

    def _compile(self, fam: _Family, buckets: Sequence[int]) -> None:
        """Execute each of ``fam``'s plans at ``buckets`` once on zeros,
        so kernel JIT is paid before traffic."""
        for op, bucket in itertools.product(fam.ops, buckets):
            plan = self._plan(fam, op, bucket)
            a_shape = fam.a_spatial(op) + (bucket,)
            jax.block_until_ready(plan.execute(
                jnp.zeros(a_shape, fam.base.dtype), fam.flt))

    def save(self, path: str) -> str:
        """Persist the plan repository as the prewarm artifact of the next
        server process (see ``prewarm(artifact=...)``)."""
        return self.registry.save(path)

    # -- request intake ----------------------------------------------------
    def submit(self, req: ConvRequest) -> ConvRequest:
        """Enqueue one request (thread-safe).  Validates the tensor against
        the registered family up front so bad requests fail loudly at
        submission, not inside a coalesced batch."""
        with self._lock:
            fam = self._layers.get(req.layer)
            warmed = self._warmed
        if fam is None:
            raise KeyError(f"unknown layer {req.layer!r}; registered: "
                           f"{sorted(self._layers)}")
        if not warmed:
            self.prewarm()
        req.op = ConvOp(req.op)
        if req.op not in fam.ops:
            raise ValueError(f"layer {req.layer!r} serves ops "
                             f"{[o.value for o in fam.ops]}, not "
                             f"{req.op.value}")
        with self.tracer.span("repro.serve.submit") as sp:
            x = jnp.asarray(req.x)
            if x.ndim == 3:
                x = x[..., None]
                req._squeeze = True
            want = fam.a_spatial(req.op)
            if x.ndim != 4 or x.shape[:3] != want:
                raise ValueError(
                    f"request {req.rid} for layer {req.layer!r} "
                    f"({req.op.value}) expects a [{want[0]}, {want[1]}, "
                    f"{want[2]}, b] tensor, got {tuple(req.x.shape)}")
            if x.shape[3] > fam.ladder[-1]:
                raise ValueError(
                    f"request {req.rid} batch {x.shape[3]} exceeds the top "
                    f"ladder bucket {fam.ladder[-1]} of layer "
                    f"{req.layer!r}; split it or raise max_batch")
            if req.deadline_s is not None and req.deadline_s <= 0:
                raise ValueError(f"request {req.rid} deadline_s must be "
                                 f"positive, got {req.deadline_s}")
            if sp:
                sp.set(b=x.shape[3])
            return self._admit(req, x, fam)

    def _admit(self, req: ConvRequest, x: jax.Array,
               fam: _Family) -> ConvRequest:
        """Cast a validated 4-D request tensor to the family's dtype, stamp
        the request and enqueue it (the tail of every ``submit``)."""
        req.x = x.astype(jnp.dtype(fam.base.dtype))
        req._b = x.shape[3]
        req.out, req.done, req.error = None, False, None
        req._event = threading.Event()
        req._t_submit = time.perf_counter()
        req._t_deadline = (req._t_submit + req.deadline_s
                           if req.deadline_s is not None else None)
        with self._lock:
            self._enqueue(req)
            self._g_queue.set(len(self._queue))
        return req

    def _enqueue(self, req: ConvRequest) -> None:
        """Append a validated request to the queue.  Called under
        ``self._lock``.  The scheduling layer overrides this with bounded
        admission control and deadline-ordered insertion."""
        self._queue.append(req)

    # -- dispatch ----------------------------------------------------------
    def _take_batch(self) -> Tuple[List[ConvRequest], Optional[str]]:
        """Pop the head request plus every queued request of the same
        (layer, op) that still fits under the family's top bucket — FIFO
        fairness across families, maximal coalescing within one.  Returns
        the group and why it flushes (``"demand"``: this server dispatches
        whatever is queued)."""
        with self._lock:
            if not self._queue:
                return [], None
            head = self._queue.popleft()
            cap = self._layers[head.layer].ladder[-1]
            group, total = [head], head._b
            for r in list(self._queue):
                if (r.layer == head.layer and r.op == head.op
                        and total + r._b <= cap):
                    self._queue.remove(r)
                    group.append(r)
                    total += r._b
            self._g_queue.set(len(self._queue))
            return group, "demand"

    def _prewarm_sharded(self, fam: _Family) -> int:
        """Mesh-mode warm: jointly select (grain x partition) for every
        (op x bucket) over the mesh's data-axis ring (``axes=("batch",)`` —
        the bucket's B axis is the coalescing axis, provably safe to split),
        register the sharded plans, and pin each chosen partition tag.
        Like ``PlanRegistry.warm`` this bumps no hit/miss counters, and an
        artifact-loaded sharded plan satisfies the warm (selection is
        deterministic, so the recomputed tag matches the stored key)."""
        built = 0
        for op in fam.ops:
            for bucket in fam.ladder:
                plan = self._build_sharded(fam.base.with_batch(bucket), op)
                k = self.registry.key(plan.scene, op, self.policy,
                                      shard=plan.shard_tag)
                if k not in self.registry:
                    built += 1
                self.registry.put(plan)
                with self._lock:
                    self._shard_tags[(fam.layer, op, bucket)] = plan.shard_tag
        return built

    def _build_sharded(self, scene: ConvScene, op: ConvOp):
        from repro.shard.plan import make_sharded_plan
        return make_sharded_plan(scene, op, policy=self.policy,
                                 devices=self._ring, axes=("batch",),
                                 model=self.cost_model)

    def _plan(self, fam: _Family, op: ConvOp, bucket: int):
        scene = fam.base.with_batch(bucket)
        if self._ring is not None:
            with self._lock:
                tag = self._shard_tags.get((fam.layer, op, bucket))
            plan = (self.registry.get(scene, op, policy=self.policy,
                                      shard=tag)
                    if tag else None)
        else:
            plan = self.registry.get(scene, op, policy=self.policy)
        if plan is None:
            self._c_plan_misses.inc()
            if self.strict:
                raise RuntimeError(
                    f"post-warm plan miss: layer {fam.layer!r} {op.value} "
                    f"bucket {bucket} is not in the registry (strict mode "
                    f"forbids steady-state plan builds)")
            # build + put directly: re-entering get_or_build would record
            # the same miss twice and deflate the registry's hit_rate
            if self._ring is not None:
                plan = self._build_sharded(scene, op)
                with self._lock:
                    self._shard_tags[(fam.layer, op, bucket)] = plan.shard_tag
            else:
                plan = make_plan(scene, op, policy=self.policy)
            self.registry.put(plan)
            self._c_plan_builds.inc()
        return plan

    def step(self) -> int:
        """Coalesce and dispatch one micro-batch; returns requests served
        (0 = queue empty).  Runs under a ``repro.serve.step`` span (args:
        the flush reason) and never waits for the device: the group's
        outputs are in flight when it returns."""
        with self.tracer.span("repro.serve.step") as sp:
            group, why = self._take_batch()
            if sp and group:
                sp.set(flush=why)
            return self._dispatch(group)

    def _bucket_for(self, fam: _Family, op: ConvOp, total: int) -> int:
        """Padded batch for a coalesced group of ``total`` lanes: the
        smallest ladder rung that fits.  The scheduling layer overrides
        this to also consider sub-rung flush buckets, priced by the cost
        model's per-bucket predictions."""
        return next(b for b in fam.ladder if b >= total)

    def _observe_wait(self, group: List[ConvRequest]) -> float:
        """Record each request's queue wait; returns the dispatch start."""
        t_start = time.perf_counter()
        for r in group:
            if r._t_submit:
                self._h_wait.observe(t_start - r._t_submit)
        return t_start

    def _gather(self, group: List[ConvRequest], bucket: int) -> jax.Array:
        """The group's tensors concatenated along B and zero-padded to
        ``bucket`` lanes (one compiled call, none for a lone full-bucket
        request), under a ``repro.serve.glue`` span."""
        with self.tracer.span("repro.serve.glue"):
            if not _needs_gather(group, bucket):
                return group[0].x
            return _gather_lanes(tuple(r.x for r in group), bucket)

    def _split(self, group: List[ConvRequest], out: jax.Array,
               bucket: int) -> Sequence[jax.Array]:
        """Each request's lanes of ``out``, squeezed where it was submitted
        3-D (one compiled call, none for a lone full-bucket 4-D request),
        under a ``repro.serve.glue`` span."""
        with self.tracer.span("repro.serve.glue"):
            if not _needs_split(group, bucket):
                return (out,)
            return _split_lanes(out, tuple(r._b for r in group),
                                tuple(r._squeeze for r in group))

    @staticmethod
    def _fail(group: List[ConvRequest], e: BaseException) -> None:
        """Complete a group that is already off the queue with ``e``, so a
        ``serve()``/``wait()`` blocked in another thread unblocks."""
        for r in group:
            r.error, r.done = e, True
            if r._event is not None:
                r._event.set()

    def _complete(self, group: List[ConvRequest], outs: Sequence[jax.Array],
                  bucket: int, total: int, t_start: float) -> None:
        """Hand each request its output (``outs``, in group order, from
        ``_split``), signal its waiters and record the dispatch's counters;
        ``enqueue_s`` ends here, outputs in flight."""
        for r, o in zip(group, outs):
            r.out, r.done = o, True
            if r._event is not None:
                r._event.set()
        self._h_enqueue.observe(time.perf_counter() - t_start)
        self._c_requests.inc(len(group))
        self._c_dispatches.inc()
        self._c_occupied.inc(total)
        self._c_bucket.inc(bucket)
        self._h_occupancy.observe(total / bucket)

    def _dispatch(self, group: List[ConvRequest]) -> int:
        """Execute one coalesced group under a ``repro.serve.dispatch``
        span; returns requests served."""
        if not group:
            return 0
        t_start = self._observe_wait(group)
        with self.tracer.span("repro.serve.dispatch") as sp:
            try:
                fam = self._layers[group[0].layer]
                op = group[0].op
                total = sum(r._b for r in group)
                bucket = self._bucket_for(fam, op, total)
                x = self._gather(group, bucket)
                plan = self._plan(fam, op, bucket)
                outs = self._split(group, plan.execute(x, fam.flt), bucket)
            except BaseException as e:  # noqa: BLE001 — propagated to every
                # waiter in the group (r.error), then re-raised
                self._fail(group, e)
                raise
            self._complete(group, outs, bucket, total, t_start)
            if sp:
                sp.set(layer=fam.layer, op=op.value, bucket=bucket,
                       occupied=total, requests=len(group))
        self._publish(DispatchRecord(
            layer=fam.layer, op=op, bucket=bucket, occupied=total,
            requests=len(group), schedule=plan.schedule))
        return len(group)

    def _publish(self, rec: DispatchRecord) -> None:
        """Deliver one record to ``on_dispatch``; a raising hook is counted
        and swallowed — an audit sink must never take serving down."""
        if self.on_dispatch is None:
            return
        try:
            self.on_dispatch(rec)
        except Exception:  # noqa: BLE001 — hook bug != dispatch failure
            self._c_hook_errors.inc()

    def drain(self) -> int:
        """Serve until the queue is empty; returns requests served."""
        served = 0
        while True:
            n = self.step()
            if n == 0:
                return served
            served += n

    def serve(self, requests: Sequence[ConvRequest]) -> List[jax.Array]:
        """Submit a burst, drain it, return outputs in request order.

        Waits on each request's completion signal, not merely on an empty
        queue: with several threads draining one server, this burst's
        requests may be mid-``execute`` inside *another* thread's step when
        our drain sees no queued work.  A request completed with an error
        (a concurrent step failed its batch) re-raises here."""
        for req in requests:
            self.submit(req)
        self.drain()
        for req in requests:
            if req._event is not None:
                req._event.wait()
            if req.error is not None:
                raise RuntimeError(
                    f"request {req.rid} failed in a coalesced dispatch"
                ) from req.error
        return [r.out for r in requests]

    # -- introspection -----------------------------------------------------
    def ladders(self) -> Dict[str, Tuple[int, ...]]:
        with self._lock:
            return {name: fam.ladder for name, fam in self._layers.items()}

    def snapshot(self) -> Dict[str, Dict]:
        """Point-in-time metric snapshot (server + registry; their metric
        names never collide) — feed it back as ``stats(since=...)`` for a
        windowed view, or persist it via ``MetricRegistry.dump``."""
        snap = dict(self.metrics.snapshot())
        snap.update(self.registry.snapshot())
        return snap

    def reset_stats(self) -> None:
        """Zero the serving and registry metrics (registrations kept)."""
        self.metrics.reset()
        self.registry.reset_stats()

    def stats(self, since: Optional[Dict] = None) -> Dict[str, float]:
        """Serving counters + the registry's.  ``occupancy`` is real lanes /
        padded lanes over all dispatches (1.0 = no pad waste);
        ``pad_waste_pct`` is its complement; ``plan_misses`` must stay 0 on
        a prewarmed server.  ``since`` (an earlier ``snapshot()``) windows
        every counter-derived field to the interval since it — this replaces
        the manual before/after arithmetic callers used to do.  ``queued``
        is instantaneous either way."""
        snap = self.snapshot()
        if since is not None:
            snap = snapshot_delta(since, snap)
        v = lambda name: int(snapshot_value(snap, f"repro.serve.{name}"))
        requests, dispatches = v("requests"), v("dispatches")
        occupied, bucket = v("occupied_lanes"), v("bucket_lanes")
        occ = occupied / bucket if bucket else 0.0
        with self._lock:
            queued = len(self._queue)
        return {
            "requests": requests,
            "dispatches": dispatches,
            "mean_batch": requests / dispatches if dispatches else 0.0,
            "occupancy": occ,
            "pad_waste_pct": 100.0 * (1.0 - occ) if bucket else 0.0,
            "occupied_lanes": occupied,
            "bucket_lanes": bucket,
            "plan_misses": v("plan_misses"),
            "plan_builds": v("plan_builds"),
            "dispatch_hook_errors": v("dispatch_hook_errors"),
            "queued": queued,
            "registry": self.registry.stats(since=since),
        }

    def describe(self) -> str:
        """One line per family: ladder and per-rung predicted efficiency."""
        model = (self.cost_model if self.cost_model is not None
                 else _active_cost_model())
        lines = []
        with self._lock:
            families = sorted(self._layers.items())
        for name, fam in families:
            effs = []
            for b in fam.ladder:
                sc = fam.base.with_batch(b)
                ch = select_schedule(sc, model=model)
                effs.append(f"{b}:{ch.schedule}"
                            f"@{predicted_efficiency(sc, ch, model):.2f}")
            lines.append(f"{name}: family[{fam.base.family_key()}] "
                         f"ladder[{' '.join(effs)}]")
        return "\n".join(lines)


def seeded_weights(scenes: Mapping[str, ConvScene],
                   weights: Optional[Mapping[str, jax.Array]] = None,
                   *, seed: int = 0) -> Dict[str, jax.Array]:
    """One FLT-layout weight per scene: the caller's where given, seeded
    random otherwise — the serving layer only needs *a* weight per layer to
    route traffic; real deployments pass trained ones."""
    out: Dict[str, jax.Array] = {}
    for i, (layer, scene) in enumerate(scenes.items()):
        if weights is not None and layer in weights:
            out[layer] = weights[layer]
        else:
            key = jax.random.PRNGKey(seed + i)
            out[layer] = jax.random.normal(
                key, scene.flt_shape(),
                jnp.float32).astype(jnp.dtype(scene.dtype))
    return out


def server_from_scenes(scenes: Mapping[str, ConvScene],
                       weights: Optional[Mapping[str, jax.Array]] = None,
                       *, seed: int = 0, ops: Sequence[ConvOp]
                       = (ConvOp.FPROP,), **kwargs) -> ConvServer:
    """Build a ``ConvServer`` straight from a layer->scene map (e.g.
    ``models.cnn.cnn_layer_scenes``); see ``seeded_weights`` for the
    missing-weight convention."""
    server = ConvServer(**kwargs)
    flts = seeded_weights(scenes, weights, seed=seed)
    for layer, scene in scenes.items():
        server.register_layer(layer, scene, flts[layer], ops=ops)
    return server
