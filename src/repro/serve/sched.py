"""Latency-aware continuous-batching scheduler over the conv serving engine.

``ConvServer`` (PR 5) optimizes exactly one operating point: steady-state
throughput.  It drains on demand, pads every dispatch up to a cost-model
bucket rung, and has no notion of latency, overload, or whole-model
requests.  ``ConvScheduler`` keeps that engine — the families, ladders,
prewarmed registry, and slice-back parity argument are unchanged — and adds
the three mechanisms production traffic needs:

**Deadline flush.**  Requests may carry ``deadline_s``.  The scheduler's
``_take_batch`` no longer fires whenever the queue is non-empty: a group
waits for its family's occupancy target (the pruned ladder's smallest kept
rung — the granularity sweet spot below which padding up is ~free), *unless*
the most urgent deadline in the group is about to expire, in which case the
group flushes at a **partial bucket**: the cheapest prewarmed power-of-two
bucket that fits, priced by the cost model's per-bucket ``predicted_s``
(the pad-waste vs. wait tradeoff made explicit — waiting longer would buy
occupancy the deadline cannot afford; padding to a pruned-away rung costs
exactly the predicted delta the pruning decision measured).  Flush buckets
come from a full (slack=0) power-of-two ladder warmed at prewarm, so a
deadline flush is still a zero-resolution registry hit — sub-rung execution
never rebuilds a plan (``PlanRegistry.warmed_buckets`` is the introspection
probe).  Deadline-less requests are bounded by ``max_gather_s`` instead, so
nothing waits forever.

**Admission control.**  The queue is bounded (``max_queue``); an arrival
beyond the bound is shed and counted (``repro.serve.shed_total``).  Policy
``"reject-newest"`` raises ``Overloaded`` at the submitter;``"edf"`` keeps
the queue earliest-deadline-first and sheds the *least urgent* request
(latest deadline, deadline-less last) — completing the victim with an
``Overloaded`` error so its waiter unblocks — which under overload converts
unbounded queue_wait growth into bounded, targeted loss.

**Whole-model sessions.**  ``register_net`` registers a *chained* scene
list (``models.cnn.cnn_chain_scenes`` / ``validate_scene_chain``) as one
pipeline; a ``ModelSession`` submits one image/batch against the net and
the scheduler carries the coalesced activation through every layer in plan
layout — layer i's coalesced OUT feeds layer i+1's IN directly, never
returning to the queue — the CNN analogue of a slot-based LM serving loop.
One bucket is chosen at entry (priced by the summed per-layer prediction),
and because B is the independent-GEMM-column axis for every layer, the
padded lanes stay zero through the whole chain and each request's columns
are bitwise identical (f32) to serving it layer-by-layer.

Deadline accounting is honest-by-construction: a dispatched group carrying
deadlines blocks on its result before ``repro.serve.deadline_misses`` /
``deadline_slack_s`` are recorded, so a "met deadline" means the tensor was
ready, not merely enqueued.  Nothing else in the loop waits for the device,
traced or not.

A whole-model group is at most three host enqueues, whatever the layer
count or group size: the gather (concat and pad), one compiled program per
(net, bucket) that runs every layer's plan and the activation — weights
are its arguments, and ``prewarm(compile=True)`` compiles it for every
flush rung (``repro.serve.chain_builds`` counts those compiles) — and the
split into each request's output.  Each layer traces under
``jax.named_scope("repro.serve.<layer>")``, so the device trace still
splits the program's time by layer through its ops' ``op_name``.

Tracing follows ``ConvServer`` (``repro.serve.conv``): ``repro.serve.step``
spans each scheduling decision and its dispatch (args: the flush reason),
``repro.serve.poll`` each idle sleep of ``drain`` and the background loop
(args: the queue depth; ``repro.serve.poll_s`` histograms its seconds), and
a whole-model group runs under ``repro.serve.model_dispatch`` (args: the
net, bucket, lanes occupied, requests, ``layers`` and ``programs``, the
host enqueues it made) with a ``repro.serve.glue`` span on each side of
the chain program's call.  So the scheduler's thread is under a
``repro.serve.*`` span at every moment but a few µs of loop overhead, and
a profiler trace names what the host did while the device idled.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
import threading
import time
from typing import (Callable, Dict, List, Mapping, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp

from repro.core.scene import ConvScene
from repro.models.cnn import validate_scene_chain
from repro.obs.metrics import snapshot_delta, snapshot_value
from repro.plan import ConvOp
from repro.serve.conv import (ConvRequest, ConvServer, DispatchRecord,
                              _Family, _needs_gather, _needs_split,
                              bucket_ladder, seeded_weights)

__all__ = ["Overloaded", "SchedConfig", "ModelRequest", "ModelSession",
           "ConvScheduler", "scheduler_from_scenes"]


class Overloaded(RuntimeError):
    """Typed admission-control rejection: the scheduler's queue is full and
    this request was shed.  Catch it at the client and back off — the
    request was never (or is no longer) queued."""


@dataclasses.dataclass(frozen=True)
class SchedConfig:
    """Scheduling knobs (the server-level ones — ladders, strictness,
    buckets — stay on ``ConvServer``).

    ``max_queue``        bounded-queue admission limit; 0 disables shedding.
    ``shed_policy``      ``"reject-newest"`` raises ``Overloaded`` at the
                         submitter; ``"edf"`` keeps the queue earliest-
                         deadline-first and sheds the least urgent entry.
    ``occupancy_target`` lanes to gather before a throughput flush; None
                         uses each family's granularity sweet spot (the
                         pruned ladder's smallest rung).
    ``max_gather_s``     how long a deadline-less group may wait for
                         occupancy before it flushes anyway.
    ``flush_margin_s``   safety margin subtracted from a deadline when
                         deciding to flush (covers dispatch overhead the
                         cost model does not price).
    ``poll_s``           idle sleep of ``drain``/the background loop when
                         nothing is flush-ready.
    """

    max_queue: int = 256
    shed_policy: str = "reject-newest"
    occupancy_target: Optional[int] = None
    max_gather_s: float = 0.05
    flush_margin_s: float = 0.002
    poll_s: float = 0.001

    def __post_init__(self):
        if self.shed_policy not in ("reject-newest", "edf"):
            raise ValueError(f"unknown shed_policy {self.shed_policy!r}; "
                             f"use 'reject-newest' or 'edf'")
        if self.max_queue < 0:
            raise ValueError("max_queue must be >= 0 (0 disables shedding)")
        if not (self.max_gather_s > 0 and math.isfinite(self.max_gather_s)):
            raise ValueError("max_gather_s must be positive and finite "
                             "(it bounds how long any request can wait)")
        if self.flush_margin_s < 0:
            raise ValueError("flush_margin_s must be >= 0")
        if self.poll_s <= 0:
            raise ValueError("poll_s must be positive")


@dataclasses.dataclass(eq=False)
class ModelRequest(ConvRequest):
    """One whole-model request: an input batch against a registered net.
    ``layer`` is the net's pseudo-family ``"@<net>"`` (so queue grouping,
    records, and metrics treat the pipeline as one family); ``x`` is the
    first layer's IN layout, ``out`` comes back in the last layer's OUT
    layout."""

    net: str = ""


@dataclasses.dataclass(frozen=True)
class _NetChain:
    """One registered net: its ordered layer names (each a registered
    family) and the optional inter-layer activation."""

    name: str
    layers: Tuple[str, ...]
    activation: Optional[Callable[[jax.Array], jax.Array]]


@dataclasses.dataclass(frozen=True)
class _ChainProgram:
    """One net's forward at one bucket, compiled: the plans it was traced
    from (a registry that hands out another plan forces a rebuild), the
    weights it is called with, and the executable."""

    plans: Tuple
    flts: Tuple[jax.Array, ...]
    run: Callable[[jax.Array, Tuple[jax.Array, ...]], jax.Array]


def _chain_forward(chain: _NetChain, plans: Sequence
                   ) -> Callable[[jax.Array, Tuple[jax.Array, ...]], jax.Array]:
    """The whole forward of ``chain`` as one traceable function of the
    input and the weights: every layer's ``plan.execute`` and the
    activation, each layer under ``jax.named_scope("repro.serve.<layer>")``
    so its ops keep the layer's name in the compiled program."""
    def serve_chain(x, ws):
        for lname, plan, w in zip(chain.layers, plans, ws):
            with jax.named_scope(f"repro.serve.{lname}"):
                x = plan.execute(x, w)
                if chain.activation is not None:
                    x = chain.activation(x)
        return x
    return serve_chain


def _urgency(r: ConvRequest) -> Tuple[float, float]:
    """EDF sort key: earliest deadline first, deadline-less requests last,
    FIFO within ties."""
    return (r._t_deadline if r._t_deadline is not None else math.inf,
            r._t_submit)


class ModelSession:
    """Client handle for whole-model requests against one registered net.
    Obtained from ``ConvScheduler.session``; thread-safe (submission goes
    through the scheduler's lock)."""

    def __init__(self, sched: "ConvScheduler", net: str):
        self._sched = sched
        self.net = net

    def submit(self, x: jax.Array, *,
               deadline_s: Optional[float] = None) -> ModelRequest:
        """Enqueue one input batch (``[inH, inW, IC, b]``, or 3-D for
        ``b = 1``); returns the live request — wait on it via
        ``ConvScheduler.wait`` or read ``.out`` after a drain."""
        req = ModelRequest(rid=next(self._sched._seq),
                           layer="@" + self.net, x=x,
                           deadline_s=deadline_s, net=self.net)
        return self._sched.submit(req)

    def serve(self, xs: Sequence[jax.Array], *,
              deadline_s: Optional[float] = None) -> List[jax.Array]:
        """Submit a burst, drain, and return outputs in request order."""
        reqs = [self.submit(x, deadline_s=deadline_s) for x in xs]
        self._sched.drain()
        return self._sched.wait(reqs)


class ConvScheduler(ConvServer):
    """Deadline-aware continuous-batching scheduler (see module docstring).

    Everything a ``ConvServer`` does still works — ``register_layer``,
    ``submit``/``drain``/``serve``, strict mode, artifacts — plus:
    ``register_net`` + ``session`` for whole-model pipelines, deadline
    flush, bounded-queue admission control, and an optional background
    loop (``start``/``stop``) for true continuous batching."""

    def __init__(self, *, config: Optional[SchedConfig] = None, **kwargs):
        if kwargs.get("mesh") is not None:
            raise ValueError(
                "ConvScheduler does not compose with mesh serving yet: "
                "sub-rung flush buckets would need per-rung sharded "
                "prewarms; use ConvServer(mesh=...) for sharded throughput "
                "serving")
        super().__init__(**kwargs)
        self.config = config if config is not None else SchedConfig()
        self._nets: Dict[str, _NetChain] = {}
        # full (slack=0) power-of-two flush ladder per layer, warmed at
        # prewarm, and the cost model's prediction per (layer, op, bucket):
        # the data behind partial-bucket pricing
        self._flush_rungs: Dict[str, Tuple[int, ...]] = {}
        self._pred_s: Dict[Tuple[str, ConvOp, int], float] = {}
        self._c_shed = self.metrics.counter("repro.serve.shed_total")
        self._c_deadline_reqs = self.metrics.counter(
            "repro.serve.deadline_requests")
        self._c_deadline_miss = self.metrics.counter(
            "repro.serve.deadline_misses")
        self._c_flush = {
            "deadline": self.metrics.counter("repro.serve.deadline_flushes"),
            "occupancy": self.metrics.counter(
                "repro.serve.occupancy_flushes"),
            "gather": self.metrics.counter(
                "repro.serve.gather_timeout_flushes"),
        }
        self._h_slack = self.metrics.histogram("repro.serve.deadline_slack_s")
        # seconds of each idle sleep of the loop: the sum is the host's
        # waiting time, the mean against poll_s its oversleep
        self._h_poll = self.metrics.histogram("repro.serve.poll_s")
        self._c_chain_builds = self.metrics.counter(
            "repro.serve.chain_builds")
        self._chains: Dict[Tuple[str, int], _ChainProgram] = {}
        self._stop_evt = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- net registration ----------------------------------------------------
    def register_net(self, net: str, scenes: Mapping[str, ConvScene],
                     weights: Optional[Mapping[str, jax.Array]] = None, *,
                     activation: Optional[Callable[[jax.Array], jax.Array]]
                     = None, seed: int = 0) -> _NetChain:
        """Register a whole-model pipeline: ``scenes`` must chain
        (``validate_scene_chain`` — ``models.cnn.cnn_chain_scenes`` builds
        chains from the paper CNNs), each scene becomes a registered layer
        family, and ``session(net)`` then serves one-shot model requests
        through all of them.  ``activation`` (e.g. ``jax.nn.relu``) is
        applied between layers; None keeps the chain linear, which makes
        session outputs bitwise comparable to per-layer serving."""
        validate_scene_chain(scenes)
        with self._lock:
            if net in self._nets:
                raise ValueError(f"net {net!r} already registered")
        flts = seeded_weights(scenes, weights, seed=seed)
        for lname, scene in scenes.items():
            self.register_layer(lname, scene, flts[lname],
                                ops=(ConvOp.FPROP,))
        chain = _NetChain(name=net, layers=tuple(scenes),
                          activation=activation)
        with self._lock:
            self._nets[net] = chain
            self._warmed = False
        return chain

    def session(self, net: str) -> ModelSession:
        """Client handle for one registered net."""
        with self._lock:
            if net not in self._nets:
                raise KeyError(f"unknown net {net!r}; registered: "
                               f"{sorted(self._nets)}")
        return ModelSession(self, net)

    def nets(self) -> Dict[str, Tuple[str, ...]]:
        with self._lock:
            return {name: chain.layers
                    for name, chain in self._nets.items()}

    # -- prewarm: flush ladders ride along -----------------------------------
    def prewarm(self, artifact: Optional[str] = None, *,
                compile: bool = False) -> int:
        """Base prewarm (every pruned-ladder plan), then warm the full
        power-of-two *flush ladder* of every family and record the cost
        model's per-bucket prediction — a deadline flush at any sub-rung
        bucket is then a pure registry hit, and partial-bucket choice is a
        dict lookup.  ``compile=True`` JIT-warms the flush rungs too, and
        compiles every registered net's chain program at every flush rung,
        so neither a deadline flush nor a model group pays a compile inside
        a latency budget."""
        built = super().prewarm(artifact, compile=compile)
        with self._lock:
            families = list(self._layers.values())
        for fam in families:
            with self._prewarm_span(fam, "flush_plans"):
                rungs = bucket_ladder(fam.base, self.max_batch,
                                      min_bucket=self.min_bucket, slack=0.0)
                built += self.registry.warm(
                    [fam.base], ops=fam.ops, buckets=rungs,
                    policy=self.policy)
                for op in fam.ops:
                    for b in rungs:
                        plan = self.registry.get(
                            fam.base.with_batch(b), op, policy=self.policy)
                        self._pred_s[(fam.layer, op, b)] = (
                            plan.predicted_s or 0.0)
                with self._lock:
                    self._flush_rungs[fam.layer] = rungs
        if compile:
            for fam in families:
                with self._prewarm_span(fam, "flush_compile"):
                    self._compile(fam, [b for b in self._flush_rungs[fam.layer]
                                        if b not in fam.ladder])
            with self._lock:
                chains = list(self._nets.values())
            for chain in chains:
                net = "@" + chain.name
                with self.tracer.span("repro.serve.prewarm", layer=net,
                                      stage="chain_compile"):
                    for b in self._flush_rungs[chain.layers[0]]:
                        self._chain_program(chain, b)
        with self._lock:
            self._warmed = True
        return built

    # -- admission control ---------------------------------------------------
    def _enqueue(self, req: ConvRequest) -> None:
        # called under self._lock (see ConvServer.submit)
        cfg = self.config
        if cfg.max_queue and len(self._queue) >= cfg.max_queue:
            victim = req
            if cfg.shed_policy == "edf":
                victim = max(itertools.chain(self._queue, (req,)),
                             key=_urgency)
            self._c_shed.inc()
            err = Overloaded(
                f"queue full ({cfg.max_queue} requests): shed request "
                f"{victim.rid} under policy {cfg.shed_policy!r}")
            if victim is req:
                raise err
            self._queue.remove(victim)
            victim.error, victim.done = err, True
            if victim._event is not None:
                victim._event.set()
        self._queue.append(req)
        if req._t_deadline is not None:
            self._c_deadline_reqs.inc()
        if cfg.shed_policy == "edf":
            ordered = sorted(self._queue, key=_urgency)
            self._queue.clear()
            self._queue.extend(ordered)

    # -- model request intake ------------------------------------------------
    def submit(self, req: ConvRequest) -> ConvRequest:
        if isinstance(req, ModelRequest):
            return self._submit_model(req)
        return super().submit(req)

    def _submit_model(self, req: ModelRequest) -> ModelRequest:
        with self._lock:
            chain = self._nets.get(req.net)
            warmed = self._warmed
        if chain is None:
            raise KeyError(f"unknown net {req.net!r}; registered: "
                           f"{sorted(self._nets)}")
        if not warmed:
            self.prewarm()
        req.layer = "@" + req.net
        req.op = ConvOp.FPROP
        fam = self._layers[chain.layers[0]]
        with self.tracer.span("repro.serve.submit") as sp:
            x = jnp.asarray(req.x)
            if x.ndim == 3:
                x = x[..., None]
                req._squeeze = True
            want = fam.a_spatial(ConvOp.FPROP)
            if x.ndim != 4 or x.shape[:3] != want:
                raise ValueError(
                    f"model request {req.rid} for net {req.net!r} expects "
                    f"a [{want[0]}, {want[1]}, {want[2]}, b] tensor, got "
                    f"{tuple(req.x.shape)}")
            if x.shape[3] > self.max_batch:
                raise ValueError(
                    f"model request {req.rid} batch {x.shape[3]} exceeds "
                    f"max_batch {self.max_batch}; split it")
            if req.deadline_s is not None and req.deadline_s <= 0:
                raise ValueError(f"request {req.rid} deadline_s must be "
                                 f"positive, got {req.deadline_s}")
            if sp:
                sp.set(b=x.shape[3])
            return self._admit(req, x, fam)

    # -- flush decision ------------------------------------------------------
    def _group_cap(self, head: ConvRequest) -> int:
        if isinstance(head, ModelRequest):
            return self.max_batch
        return self._layers[head.layer].ladder[-1]

    def _occupancy_target(self, head: ConvRequest) -> int:
        if self.config.occupancy_target:
            return self.config.occupancy_target
        if isinstance(head, ModelRequest):
            # the chain runs every layer at the chosen bucket, so gather to
            # the most demanding layer's sweet spot — padding is only free
            # when it is free for every layer in the pipeline
            chain = self._nets[head.net]
            return max(self._layers[l].ladder[0] for l in chain.layers)
        return self._layers[head.layer].ladder[0]

    def _flush_bucket(self, head: ConvRequest, total: int) -> int:
        first = (self._nets[head.net].layers[0]
                 if isinstance(head, ModelRequest) else head.layer)
        rungs = self._flush_rungs.get(first, ())
        return next((b for b in rungs if b >= total), total)

    def _predicted_dispatch_s(self, head: ConvRequest, bucket: int) -> float:
        if isinstance(head, ModelRequest):
            chain = self._nets[head.net]
            return sum(self._pred_s.get((l, ConvOp.FPROP, bucket), 0.0)
                       for l in chain.layers)
        return self._pred_s.get((head.layer, head.op, bucket), 0.0)

    def _peek_group(self, head: ConvRequest
                    ) -> Tuple[List[ConvRequest], int]:
        # called under self._lock; non-destructive coalescing preview
        cap = self._group_cap(head)
        group, total = [head], head._b
        for r in self._queue:
            if r is head:
                continue
            if (r.layer == head.layer and r.op == head.op
                    and total + r._b <= cap):
                group.append(r)
                total += r._b
        return group, total

    def _flush_reason(self, head: ConvRequest, group: List[ConvRequest],
                      total: int, now: float) -> Optional[str]:
        """Why this group should dispatch now — or None to keep gathering.
        ``"occupancy"``: the family's sweet-spot rung is filled (the
        throughput path, identical to what drain-on-demand would batch).
        ``"deadline"``: the most urgent deadline cannot afford to wait for
        the predicted flush-bucket execution plus margin.  ``"gather"``:
        deadline-less requests have waited ``max_gather_s``."""
        cfg = self.config
        if total >= min(self._occupancy_target(head), self._group_cap(head)):
            return "occupancy"
        deadlines = [r._t_deadline for r in group
                     if r._t_deadline is not None]
        if deadlines:
            pred = self._predicted_dispatch_s(
                head, self._flush_bucket(head, total))
            if min(deadlines) - now <= pred + cfg.flush_margin_s:
                return "deadline"
        if now - min(r._t_submit for r in group) >= cfg.max_gather_s:
            return "gather"
        return None

    def _take_batch(self) -> Tuple[List[ConvRequest], Optional[str]]:
        """First flush-ready group in queue order (EDF policy keeps the
        queue deadline-ordered, so "queue order" is urgency order there)
        and its flush reason; an empty list when nothing should dispatch
        yet."""
        now = time.perf_counter()
        with self._lock:
            if not self._queue:
                return [], None
            seen = set()
            for head in list(self._queue):
                key = (head.layer, head.op)
                if key in seen:
                    continue
                seen.add(key)
                group, total = self._peek_group(head)
                why = self._flush_reason(head, group, total, now)
                if why is None:
                    continue
                for r in group:
                    self._queue.remove(r)
                self._g_queue.set(len(self._queue))
                self._c_flush[why].inc()
                return group, why
            return [], None

    # -- dispatch ------------------------------------------------------------
    def _bucket_for(self, fam: _Family, op: ConvOp, total: int) -> int:
        """Cheapest warmed bucket that fits, by the cost model's per-bucket
        prediction (ties to the smaller pad).  Compute-bound rungs predict
        flat, so this picks the smallest power-of-two fit; a memory-bound
        family pays per lane and likewise prefers minimal padding — either
        way a sub-rung flush never pays for lanes the deadline didn't buy."""
        rungs = [b for b in self._flush_rungs.get(fam.layer, ())
                 if b >= total]
        if not rungs:
            return super()._bucket_for(fam, op, total)
        return min(rungs,
                   key=lambda b: (self._pred_s.get((fam.layer, op, b), 0.0),
                                  b))

    def step(self) -> int:
        """One scheduling decision + dispatch under a ``repro.serve.step``
        span (args: the flush reason); returns requests served (0 = nothing
        flush-ready)."""
        with self.tracer.span("repro.serve.step") as sp:
            group, why = self._take_batch()
            if not group:
                return 0
            if sp:
                sp.set(flush=why)
            if isinstance(group[0], ModelRequest):
                served = self._dispatch_model(group)
            else:
                served = self._dispatch(group)
            self._account_deadlines(group)
            return served

    def _account_deadlines(self, group: List[ConvRequest]) -> None:
        deadlined = [r for r in group if r._t_deadline is not None]
        if not deadlined:
            return
        if group[0].out is not None:
            # dispatch is async; block on one lane (the group shares a
            # dispatch) so miss accounting measures completion, not
            # enqueue — deadline-carrying traffic opts into the sync
            jax.block_until_ready(group[0].out)
        now = time.perf_counter()
        for r in deadlined:
            slack = r._t_deadline - now
            self._h_slack.observe(slack)
            if slack < 0:
                self._c_deadline_miss.inc()

    def _model_bucket(self, chain: _NetChain, total: int) -> int:
        rungs = [b for b in self._flush_rungs.get(chain.layers[0], ())
                 if b >= total]
        if not rungs:
            raise RuntimeError(
                f"net {chain.name!r} has no warmed flush bucket >= {total}; "
                f"prewarm() the scheduler before serving model requests")
        cost = lambda b: sum(
            self._pred_s.get((l, ConvOp.FPROP, b), 0.0)
            for l in chain.layers)
        return min(rungs, key=lambda b: (cost(b), b))

    def _chain_program(self, chain: _NetChain, bucket: int) -> _ChainProgram:
        """The compiled forward of ``chain`` at ``bucket`` lanes.  The plans
        are looked up on every call, so a strict-mode miss still raises here
        and the registry's counters see each dispatch; the program is
        compiled once per (net, bucket) and again only when the registry
        hands out another plan (``repro.serve.chain_builds``)."""
        fams = [self._layers[l] for l in chain.layers]
        plans = tuple(self._plan(f, ConvOp.FPROP, bucket) for f in fams)
        key = (chain.name, bucket)
        with self._lock:
            prog = self._chains.get(key)
        if prog is not None and prog.plans == plans:
            return prog
        flts = tuple(f.flt for f in fams)
        x = jax.ShapeDtypeStruct(fams[0].a_spatial(ConvOp.FPROP) + (bucket,),
                                 jnp.dtype(fams[0].base.dtype))
        run = jax.jit(_chain_forward(chain, plans)).lower(x, flts).compile()
        prog = _ChainProgram(plans=plans, flts=flts, run=run)
        with self._lock:
            self._chains[key] = prog
        self._c_chain_builds.inc()
        return prog

    def _dispatch_model(self, group: List[ConvRequest]) -> int:
        """Execute one coalesced whole-model group in at most three host
        enqueues: the gather, the (net, bucket) chain program, the split.
        Mirrors ``ConvServer._dispatch``'s tracing and completion
        contract."""
        t_start = self._observe_wait(group)
        chain = self._nets[group[0].net]
        with self.tracer.span("repro.serve.model_dispatch") as sp:
            try:
                total = sum(r._b for r in group)
                bucket = self._model_bucket(chain, total)
                prog = self._chain_program(chain, bucket)
                x = self._gather(group, bucket)
                outs = self._split(group, prog.run(x, prog.flts), bucket)
            except BaseException as e:  # noqa: BLE001 — propagated to every
                # waiter in the group (r.error), then re-raised
                self._fail(group, e)
                raise
            self._complete(group, outs, bucket, total, t_start)
            if sp:
                sp.set(net=chain.name, bucket=bucket, occupied=total,
                       requests=len(group), layers=len(chain.layers),
                       programs=1 + _needs_gather(group, bucket)
                       + _needs_split(group, bucket))
        self._publish(DispatchRecord(
            layer=group[0].layer, op=ConvOp.FPROP, bucket=bucket,
            occupied=total, requests=len(group), schedule=None))
        return len(group)

    # -- serving loops -------------------------------------------------------
    def drain(self) -> int:
        """Serve until the queue is empty.  Unlike the base server an
        unflushed queue is not an empty one: when nothing is flush-ready
        yet, sleep ``poll_s`` and retry — ``max_gather_s`` bounds how long
        any group can sit unflushed, so this terminates."""
        served = 0
        while True:
            n = self.step()
            served += n
            if n:
                continue
            with self._lock:
                if not self._queue:
                    return served
            self._poll()

    def _poll(self) -> None:
        """Sleep ``poll_s`` while nothing is flush-ready, under a
        ``repro.serve.poll`` span (args: the queue depth); the seconds
        slept go to ``repro.serve.poll_s``."""
        with self.tracer.span("repro.serve.poll") as sp:
            if sp:
                sp.set(depth=len(self._queue))
            t0 = time.perf_counter()
            time.sleep(self.config.poll_s)
            self._h_poll.observe(time.perf_counter() - t0)

    def wait(self, requests: Sequence[ConvRequest], *,
             raise_on_error: bool = True) -> List[Optional[jax.Array]]:
        """Block until every request completes; returns outputs in request
        order.  ``raise_on_error=False`` returns None for failed/shed
        requests instead of re-raising (bulk clients inspect ``.error``)."""
        outs: List[Optional[jax.Array]] = []
        for r in requests:
            if r._event is not None:
                r._event.wait()
            if r.error is not None and raise_on_error:
                raise RuntimeError(
                    f"request {r.rid} failed in a coalesced dispatch"
                ) from r.error
            outs.append(r.out)
        return outs

    def start(self) -> None:
        """Run the scheduling loop in a daemon thread — continuous
        batching: clients just ``submit`` and ``wait``."""
        with self._lock:
            if self._thread is not None:
                raise RuntimeError("scheduler loop already running")
            warmed = self._warmed
        if not warmed:
            self.prewarm()
        self._stop_evt.clear()
        t = threading.Thread(target=self._loop, name="repro-serve-sched",
                             daemon=True)
        with self._lock:
            self._thread = t
        t.start()

    def _loop(self) -> None:
        while not self._stop_evt.is_set():
            try:
                if self.step() == 0:
                    self._poll()
            except Exception:  # noqa: BLE001 — the failed group's waiters
                # already carry the error (step completed them before
                # re-raising); the loop must keep serving everyone else
                continue

    def stop(self) -> None:
        """Stop the background loop (queued work stays queued)."""
        self._stop_evt.set()
        with self._lock:
            t = self._thread
            self._thread = None
        if t is not None:
            t.join()

    # -- introspection -------------------------------------------------------
    def stats(self, since: Optional[Dict] = None) -> Dict[str, float]:
        """Base serving stats plus scheduler health: shed/deadline counters
        and the flush-reason breakdown (same ``since`` windowing)."""
        s = super().stats(since=since)
        snap = self.snapshot()
        if since is not None:
            snap = snapshot_delta(since, snap)
        v = lambda name: int(snapshot_value(snap, f"repro.serve.{name}"))
        dl = v("deadline_requests")
        s.update({
            "shed": v("shed_total"),
            "deadline_requests": dl,
            "deadline_misses": v("deadline_misses"),
            "deadline_miss_rate": v("deadline_misses") / dl if dl else 0.0,
            "deadline_flushes": v("deadline_flushes"),
            "occupancy_flushes": v("occupancy_flushes"),
            "gather_timeout_flushes": v("gather_timeout_flushes"),
        })
        return s

    def flush_ladders(self) -> Dict[str, Tuple[int, ...]]:
        """Per-layer warmed flush rungs (the sub-rung dispatch menu)."""
        with self._lock:
            return dict(self._flush_rungs)


def scheduler_from_scenes(scenes: Mapping[str, ConvScene],
                          weights: Optional[Mapping[str, jax.Array]] = None,
                          *, seed: int = 0,
                          ops: Sequence[ConvOp] = (ConvOp.FPROP,),
                          config: Optional[SchedConfig] = None,
                          **kwargs) -> ConvScheduler:
    """``server_from_scenes`` for the scheduler: build a ``ConvScheduler``
    from a layer -> scene map, seeding missing weights."""
    sched = ConvScheduler(config=config, **kwargs)
    flts = seeded_weights(scenes, weights, seed=seed)
    for layer, scene in scenes.items():
        sched.register_layer(layer, scene, flts[layer], ops=ops)
    return sched
