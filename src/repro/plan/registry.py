"""Process-level plan repository — plan-once, execute-many, serve-forever.

``PlanRegistry`` memoizes frozen ``ConvPlan``s under a canonical signature
(scene dims + dtype + op + policy), with the same
conventions as the tune subsystem's schedule cache: hit/miss counters,
bounded LRU eviction, and a versioned JSON artifact (atomic tmp+rename
merge-on-``save`` so concurrent writers union rather than clobber,
merge-on-``load``) so serving processes and benchmarks can
warm-start a plan repository the way ``repro.tune`` warm-starts schedule
selection.  Loading never re-runs schedule resolution: stored choices are
pinned exactly (``build.assemble_plan``).
"""
from __future__ import annotations

import collections
import json
import os
import sys
import tempfile
import threading
import time
from typing import Dict, Iterable, Optional, Sequence, Union

import jax.numpy as jnp

from repro.core.scene import ConvScene
from repro.obs.metrics import MetricRegistry, snapshot_delta, snapshot_value
from repro.obs.trace import default_tracer
from repro.plan.build import (ConvOp, ConvPlan, PolicySpec, assemble_plan,
                              make_plan, policy_tag)
from repro.tune.cache import choice_from_dict, choice_to_dict

# Bump when plan semantics / the artifact layout change meaning.
PLAN_VERSION = "mg3m-plan-v1"
_SCHEMA = 1

_SCENE_FIELDS = ("B", "IC", "OC", "inH", "inW", "fltH", "fltW",
                 "padH", "padW", "stdH", "stdW", "dtype",
                 "dilH", "dilW", "fdilH", "fdilW", "apadH", "apadW")


def plan_signature(scene: ConvScene, op: Union[ConvOp, str],
                   policy: PolicySpec,
                   shard: Optional[str] = None) -> str:
    """Canonical registry key.  Dtype-alias-stable (via numpy dtype names)
    and explicit about everything that changes the executable.  Dilation
    axes are appended only when active, so undilated keys — the entire
    pre-dilation artifact population — stay byte-identical.  ``shard`` is a
    ``ShardSpec.tag`` (``axis:n``, e.g. ``"h:8"``); appended only when set,
    so unsharded keys likewise stay byte-identical and a sharded plan never
    shadows its single-device sibling (``"none:1"`` — the joint selector's
    fallback — is still a distinct key: same numerics, different wrapper)."""
    dt = jnp.dtype(scene.dtype).name
    frag = f"|shard={shard}" if shard else ""
    return (f"v={PLAN_VERSION}|op={ConvOp(op).value}|pol={policy_tag(policy)}"
            f"|dt={dt}"
            f"|B={scene.B}|IC={scene.IC}|OC={scene.OC}"
            f"|in={scene.inH}x{scene.inW}|flt={scene.fltH}x{scene.fltW}"
            f"|pad={scene.padH},{scene.padW}|std={scene.stdH},{scene.stdW}"
            f"{scene.dilation_suffix()}{frag}")


def plan_to_dict(plan) -> Dict:
    d = {
        "scene": {f: getattr(plan.scene, f) for f in _SCENE_FIELDS},
        "op": plan.op.value,
        "policy": plan.policy,
        "uses_reference": plan.uses_reference,
        "notes": list(plan.notes),
        "choice": choice_to_dict(plan.choice) if plan.choice else None,
    }
    tag = getattr(plan, "shard_tag", None)
    if tag:
        # sharded identity: partition axis + ring size; cost/geometry terms
        # are recomputed on reload (pinned_shard_spec), never trusted
        d["shard"] = {"axis": plan.spec.axis, "n": plan.spec.n_shards}
    return d


def plan_from_dict(d: Dict):
    """Rebuild a plan from its artifact entry — no schedule resolution.
    Sharded entries rebuild through ``assemble_sharded_plan`` and raise
    ``ValueError`` when this process has fewer devices than the stored
    ring (``load`` skips them, ``save`` keeps them — see
    ``valid_plan_dict``).  The ``interpret`` field of artifacts written
    before the kernel mode was derived from the platform is ignored, and
    so is ``use_pallas: true`` from before every plan ran the kernels; an
    entry stored with ``use_pallas: false`` describes a plan no longer
    built and raises ``ValueError``."""
    if not d.get("use_pallas", True):
        raise ValueError("stored plan has use_pallas=false: the reference "
                         "is no longer a plan mode")
    scene = ConvScene(**d["scene"])
    sh = d.get("shard")
    if sh:
        from repro.shard.plan import assemble_sharded_plan
        choice = choice_from_dict(d["choice"])
        return assemble_sharded_plan(scene, d["op"], d["policy"],
                                     sh["axis"], int(sh["n"]), choice)
    choice = choice_from_dict(d["choice"]) if d.get("choice") else None
    return assemble_plan(scene, d["op"], d["policy"], choice)


def entry_key(d: Dict) -> str:
    """Registry key of one stored entry, recomputed from its fields: an
    artifact whose keys carry a fragment the signature no longer has (the
    ``|int=`` kernel mode, the ``|pl=`` knob) still serves its plans under
    today's keys."""
    sh = d.get("shard")
    return plan_signature(ConvScene(**d["scene"]), d["op"], d["policy"],
                          shard=f"{sh['axis']}:{int(sh['n'])}" if sh else None)


def valid_plan_dict(d) -> bool:
    """Validity check for one stored plan entry (the ``tune/cache.py``
    ``valid_record`` analogue): an entry is valid iff ``plan_from_dict``
    can actually rebuild it — anything ``load()`` would skip with a
    warning must also be dropped by merge-on-``save``, or the dead entry
    rides the artifact forever and warn-spams every warm-start.  Cheap for
    well-formed entries: a pinned choice assembles without any schedule
    resolution, and a choice-less (reference) entry short-circuits before
    the selector.

    One deliberate asymmetry: a *sharded* entry is validated structurally
    (identity re-derives), not by binding a device ring — the ring is an
    environment property, and an 8-shard plan saved by an 8-device host
    must survive a 1-device process's merge-on-save even though that
    process's ``load`` skips it."""
    if not isinstance(d, dict):
        return False
    if d.get("shard"):
        try:
            from repro.shard.plan import pinned_shard_spec
            pinned_shard_spec(ConvScene(**d["scene"]), d["op"],
                              d["shard"]["axis"], int(d["shard"]["n"]),
                              choice_from_dict(d["choice"]))
            return True
        except (KeyError, TypeError, ValueError):
            return False
    try:
        plan_from_dict(d)
        return True
    except (KeyError, TypeError, ValueError):
        return False


class PlanRegistry:
    """LRU-bounded map: plan signature -> frozen ``ConvPlan``.

    Thread-safe: every public operation holds one reentrant lock, so
    concurrent submitters (a serving process coalescing traffic from many
    client threads) can't corrupt the ``OrderedDict`` LRU mid-``move_to_end``
    or under-count the hit/miss/eviction stats (``+= 1`` on an attribute is
    a read-modify-write race without it).  ``get_or_build`` holds the lock
    across the build too: two threads racing the same miss produce one plan,
    one miss, and one identical object — never a duplicate ``make_plan``.
    The lock also spans ``save``'s read-merge-write window, so two threads
    of one process can't interleave their merges (cross-*process* saves
    remain lock-free merge-on-save, as documented on ``save``).
    """

    def __init__(self, *, max_plans: int = 1024,
                 metrics: Optional[MetricRegistry] = None):
        self.max_plans = max_plans
        self._mem: "collections.OrderedDict[str, ConvPlan]" = \
            collections.OrderedDict()
        self._lock = threading.RLock()
        # Stats live in a MetricRegistry (own one by default, shareable via
        # ``metrics=``): snapshot/delta/reset come from the obs layer
        # instead of bespoke arithmetic; ``hits``/``misses``/``evictions``
        # remain readable as attributes for existing callers.
        self.metrics = metrics if metrics is not None else MetricRegistry()
        self._c_hits = self.metrics.counter("repro.plan.registry.hits")
        self._c_misses = self.metrics.counter("repro.plan.registry.misses")
        self._c_evictions = self.metrics.counter(
            "repro.plan.registry.evictions")
        self._c_builds = self.metrics.counter("repro.plan.registry.builds")

    @property
    def hits(self) -> int:
        return int(self._c_hits.value)

    @property
    def misses(self) -> int:
        return int(self._c_misses.value)

    @property
    def evictions(self) -> int:
        return int(self._c_evictions.value)

    def __len__(self) -> int:
        with self._lock:
            return len(self._mem)

    def __contains__(self, key: str) -> bool:
        with self._lock:
            return key in self._mem

    def key(self, scene: ConvScene, op: Union[ConvOp, str] = ConvOp.FPROP,
            policy: PolicySpec = "analytic",
            shard: Optional[str] = None) -> str:
        return plan_signature(scene, op, policy, shard)

    # -- lookup ------------------------------------------------------------
    def get(self, scene: ConvScene, op: Union[ConvOp, str] = ConvOp.FPROP, *,
            policy: PolicySpec = "analytic",
            shard: Optional[str] = None):
        """Registered plan, or None on miss (LRU-touching).  ``shard`` is a
        ``ShardSpec.tag`` and selects the mesh-sharded entry population
        (``ShardedConvPlan``); ``None`` addresses unsharded plans only."""
        k = self.key(scene, op, policy, shard)
        with self._lock:
            plan = self._mem.get(k)
            if plan is None:
                self._c_misses.inc()
                return None
            self._mem.move_to_end(k)
            self._c_hits.inc()
            return plan

    def put(self, plan) -> str:
        k = plan_signature(plan.scene, plan.op, plan.policy,
                           shard=getattr(plan, "shard_tag", None))
        with self._lock:
            self._mem[k] = plan
            self._mem.move_to_end(k)
            self._evict()
        return k

    def get_or_build(self, scene: ConvScene,
                     op: Union[ConvOp, str] = ConvOp.FPROP, *,
                     policy: PolicySpec = "analytic") -> ConvPlan:
        """The plan-once entry: registry hit, or ``make_plan`` + register.
        Atomic under the registry lock: concurrent callers racing the same
        miss serialize through one build and all receive the same plan.
        Holding the lock across the build is deliberate: ``make_plan``
        never measures (even ``policy="tuned"`` is a cache lookup with an
        analytic fallback), so the critical section is bounded by selector
        math — cheap enough that same-key dedup beats per-key locking."""
        with self._lock:
            plan = self.get(scene, op, policy=policy)
            if plan is None:
                plan = make_plan(scene, op, policy=policy)
                self._c_builds.inc()
                self.put(plan)
            return plan

    def warm(self, scenes: Iterable[ConvScene],
             ops: Sequence[Union[ConvOp, str]] = (ConvOp.FPROP,),
             buckets: Optional[Sequence[int]] = None, *,
             policy: PolicySpec = "analytic") -> int:
        """Pre-build every (scene x op x bucket) plan not already registered;
        returns how many were built.  ``buckets`` rebatches each scene to
        every given batch size (``ConvScene.with_batch``) — the serving
        bucket-ladder warm path; ``None`` keeps each scene's own batch.

        On return the *entire* warmed set is resident: already-present keys
        are LRU-touched (not skipped), so this warm's plans are the most
        recently used and eviction falls on unrelated entries first; a
        warmed set larger than ``max_plans`` raises ``ValueError`` up front
        rather than silently evicting plans it just built (a strict server
        would pass prewarm and then miss on the first request).

        Warming is deliberate, not traffic: it bumps neither ``hits`` nor
        ``misses``, so "zero plan misses after prewarm" is assertable from
        ``stats()`` without snapshot arithmetic."""
        built = 0
        with self._lock:
            work = []
            for scene in scenes:
                for b in (buckets if buckets else (scene.B,)):
                    rebatched = scene.with_batch(b)
                    for op in ops:
                        work.append((rebatched, op,
                                     self.key(rebatched, op, policy)))
            if len({k for _, _, k in work}) > self.max_plans:
                raise ValueError(
                    f"cannot warm {len({k for _, _, k in work})} plans into "
                    f"a registry bounded at max_plans={self.max_plans}: the "
                    f"LRU would evict part of the warmed set before it is "
                    f"ever served; raise max_plans or shrink the "
                    f"(scenes x ops x buckets) ladder")
            for rebatched, op, k in work:
                if k not in self._mem:
                    self._mem[k] = make_plan(rebatched, op, policy=policy)
                    self._c_builds.inc()
                    built += 1
                self._mem.move_to_end(k)
            self._evict()
        return built

    def _evict(self) -> None:
        # callers hold self._lock (all public entry points do)
        while len(self._mem) > self.max_plans:
            self._mem.popitem(last=False)  # least-recently used
            self._c_evictions.inc()

    def clear(self) -> None:
        with self._lock:
            self._mem.clear()

    def snapshot(self) -> Dict[str, Dict]:
        """Point-in-time metrics snapshot — pass back as ``stats(since=...)``
        to read a *window* instead of lifetime aggregates."""
        return self.metrics.snapshot()

    def reset_stats(self) -> None:
        """Zero hit/miss/eviction/build counters (plans stay resident)."""
        self.metrics.reset()

    def stats(self, since: Optional[Dict] = None) -> Dict[str, float]:
        """Counter view; with ``since`` (a prior ``snapshot()``) every
        counter and the hit rate describe only the window since then —
        no manual before/after arithmetic at call sites."""
        snap = self.metrics.snapshot()
        if since is not None:
            snap = snapshot_delta(since, snap)
        v = lambda name: int(snapshot_value(snap,
                                            f"repro.plan.registry.{name}"))
        hits, misses = v("hits"), v("misses")
        lookups = hits + misses
        return {"size": len(self), "hits": hits, "misses": misses,
                "evictions": v("evictions"), "builds": v("builds"),
                "hit_rate": hits / lookups if lookups else 0.0}

    def plans(self) -> Dict[str, ConvPlan]:
        """Snapshot of signature -> plan."""
        with self._lock:
            return dict(self._mem)

    def warmed_buckets(self, scene: ConvScene,
                       op: Union[ConvOp, str] = ConvOp.FPROP, *,
                       policy: PolicySpec = "analytic") -> tuple:
        """Every batch size of ``scene``'s family resident for ``op`` under
        ``policy``, ascending.  This is the sub-rung execution
        probe for the scheduling layer: a deadline flush may execute any
        warmed bucket without a steady-state resolution, so "which buckets
        are free to dispatch at" is a registry question, not a ladder one.
        A peek, not traffic: bumps neither hits nor misses and touches no
        LRU order."""
        op = ConvOp(op)
        pol = policy_tag(policy)
        base = scene.with_batch(1)
        out = []
        with self._lock:
            for plan in self._mem.values():
                if (plan.op is op and plan.policy == pol
                        and getattr(plan, "shard_tag", None) is None
                        and plan.scene.with_batch(1) == base):
                    out.append(plan.scene.B)
        return tuple(sorted(set(out)))

    # -- persistence -------------------------------------------------------
    def save(self, path: str) -> str:
        """Merge-on-save: union our plans with whatever is on disk, then
        write atomically (tmp+rename) — the ``tune/cache.py`` convention.

        Two serving processes saving to the same artifact union rather than
        blind-overwrite: the read-modify-write happens inside this call,
        our in-memory plan wins a key collision (it is at least as fresh),
        and disk-only keys — another writer's plans, or entries beyond our
        LRU bound — ride along.  Like the tune cache this is lock-free:
        saves whose read windows overlap can still lose keys the other
        writer added in between (last rename wins); the merge closes the
        common sequential-clobber case, it is not a locking guarantee."""
        p = os.path.abspath(os.path.expanduser(path))
        t0 = time.perf_counter()
        with default_tracer().span("repro.plan.registry.save", path=p):
            with self._lock:
                out = self._save_locked(p)
        self.metrics.histogram("repro.plan.registry.save_s").observe(
            time.perf_counter() - t0)
        return out

    def _save_locked(self, p: str) -> str:
        plans = {k: plan_to_dict(pl) for k, pl in self._mem.items()}
        if os.path.exists(p):
            try:
                with open(p) as f:
                    doc = json.load(f)
                on_disk = doc.get("plans", {}) if isinstance(doc, dict) else {}
                if not isinstance(on_disk, dict):
                    on_disk = {}
            except (json.JSONDecodeError, OSError):
                on_disk = {}   # corrupt artifact: overwrite with our state
            for d in on_disk.values():
                if valid_plan_dict(d):   # drop malformed disk entries
                    plans.setdefault(entry_key(d), d)
        doc = {"schema": _SCHEMA, "version": PLAN_VERSION, "plans": plans}
        os.makedirs(os.path.dirname(p) or ".", exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(p) or ".",
                                   suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
            os.replace(tmp, p)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        return p

    def load(self, path: str) -> int:
        """Merge plans from an artifact; returns how many were loaded.
        Malformed or stale entries are skipped with a warning, never fatal —
        a hand-edited artifact must not brick a serving warm-start."""
        p = os.path.abspath(os.path.expanduser(path))
        t0 = time.perf_counter()
        loaded = 0
        skipped = []
        with default_tracer().span("repro.plan.registry.load", path=p):
            with open(p) as f:
                doc = json.load(f)
            with self._lock:
                for k, d in doc.get("plans", {}).items():
                    try:
                        plan = plan_from_dict(d)
                        k = entry_key(d)
                    except (KeyError, TypeError, ValueError) as e:
                        skipped.append((k, e))
                        continue
                    self._mem[k] = plan
                    self._mem.move_to_end(k)
                    loaded += 1
                self._evict()
        self.metrics.histogram("repro.plan.registry.load_s").observe(
            time.perf_counter() - t0)
        if skipped:
            print(f"repro.plan: skipped {len(skipped)} malformed plan "
                  f"entr{'y' if len(skipped) == 1 else 'ies'} in {p} "
                  f"(first: {skipped[0][0]!r}: {skipped[0][1]})",
                  file=sys.stderr)
        return loaded


# -- process-wide default registry ------------------------------------------
_default: Optional[PlanRegistry] = None


def default_registry() -> PlanRegistry:
    global _default
    if _default is None:
        _default = PlanRegistry()
    return _default


def set_default_registry(registry: Optional[PlanRegistry]) -> None:
    """Install (or with None, reset) the process-wide registry — used by
    serving warm-start code and tests."""
    global _default
    _default = registry


def get_plan(scene: ConvScene, op: Union[ConvOp, str] = ConvOp.FPROP, *,
             policy: PolicySpec = "analytic",
             registry: Optional[PlanRegistry] = None) -> ConvPlan:
    """Plan-once convenience on the default (or given) registry."""
    reg = registry if registry is not None else default_registry()
    return reg.get_or_build(scene, op, policy=policy)
