"""Mesh-sharded ConvPlan execution — ``shard_map`` around per-shard plans.

``ShardedConvPlan`` is the mesh-aware sibling of ``plan.build.ConvPlan``:
same frozen plan-once / execute-many contract, same global-array
``execute(a, b)`` signature and op semantics, but the dispatch runs the
per-shard ``ConvPlan`` under ``jax.shard_map`` over the plan's whole
device pool, laid out as ``("shard", "rep")``: ``n_shards`` partitions,
each replicated on ``len(pool) // n_shards`` devices (the ``n_shards == 1``
fallback runs replicated on every device).  Every plan built for one pool
therefore shares one device assignment, which a jitted model needs: a
Mosaic kernel cannot be partitioned automatically, so an unwrapped plan,
or one on a sub-ring, cannot sit in a program over the pool.  The
``jax.lax`` collectives are wired per partition axis:

  batch / oc   pure data decomposition over independent GEMM columns /
               rows — no collective, bitwise-identical (f32) to the
               unsharded plan;
  h            the globally pre-padded input is split into per-shard row
               chunks; each shard gathers its halo rows from the next
               shard(s) by ``lax.ppermute`` ring rotation (rows past the
               partitioned extent ride a small replicated tail buffer and
               are selected by ``lax.axis_index``) — bitwise-identical,
               because every output row is still produced by one shard's
               ordinary kernel accumulation;
  ic           every shard convolves its reduction slice into a full-size
               partial output and ``lax.psum`` ring-reduces — within
               tolerance (float addition reorders across shards).

All three directions route through the same wrapper: DGRAD and WGRAD
reuse the exact operand transforms of the in-process executors
(``plan.build.dgrad_operands`` / ``wgrad_operands`` / ``wgrad_finish``),
so the per-shard plan is always an *fprop-form* plan over the partition's
sub-exec-scene and the partition axes mean the same thing for every op.
``sharded_conv_with_plans`` (see ``repro.shard.autodiff``) closes the
loop: a ``custom_vjp`` whose backward passes are themselves sharded
plans.

Uneven partitions zero-pad the partitioned dim up to ``n * sub_dim`` and
slice the result back — zero lanes are linear-safe (the serving layer's
bucket-padding argument), so remainder shards cost padding, not a
special-cased geometry.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional, Sequence, Tuple, Union

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.mapping import (SHARD_LAUNCH_OVERHEAD_S, SCHEDULES,
                                CostModel, ScheduleChoice)
from repro.core.scene import ConvScene
from repro.obs.metrics import default_metrics
from repro.obs.trace import default_tracer
from repro.plan.build import (ConvOp, ConvPlan, PolicySpec, _IO_SHAPES,
                              _active_cost_model, _pad_axis, dgrad_operands,
                              grad_filter_scene, grad_input_scene, make_plan,
                              policy_tag, wgrad_finish, wgrad_operands)
from repro.shard.spec import (PARTITION_AXES, UNSHARDED_AXIS, ShardSpec,
                              collective_bytes, collective_seconds,
                              halo_geometry, select_shard_spec,
                              shard_sub_scene)

#: check_vma=False: pallas_call has no varying-manual-axes rule.
_SHMAP = functools.partial(jax.shard_map, check_vma=False)


@dataclasses.dataclass(frozen=True)
class ShardedConvPlan:
    """Frozen mesh-sharded plan for one (scene, op, policy, partition).

    ``execute`` takes and returns *global* (unsharded) arrays with the
    same shapes as the equivalent ``ConvPlan`` — callers swap one in
    without touching their data flow.  ``inner`` is the per-shard plan:
    an fprop-form ``ConvPlan`` over ``spec.sub_scene`` (which equals the
    exec scene when the selector fell back to ``n_shards == 1``).
    """

    scene: ConvScene                  # the *forward* scene the plan serves
    op: ConvOp
    policy: str                       # canonical tag (requested policy)
    spec: ShardSpec
    inner: ConvPlan                   # fprop-form plan over spec.sub_scene
    exec_scene: ConvScene             # the full (unpartitioned) exec scene
    devices: Tuple[object, ...]       # the pool; spec.n_shards divides it
    out_hw: Tuple[int, int] = (0, 0)  # wgrad spatial slice-back (0,0 = none)

    # -- execution ---------------------------------------------------------
    def execute(self, a: jax.Array, b: jax.Array) -> jax.Array:
        """Run the planned op on global arrays: (inp, flt) for FPROP,
        (d_out, flt) for DGRAD, (inp, d_out) for WGRAD."""
        a_shape, b_shape, _ = self.io_shapes()
        if a.shape != a_shape or b.shape != b_shape:
            raise ValueError(
                f"sharded {self.op.value} plan for {self.scene.describe()} "
                f"expects operands {a_shape} x {b_shape}, got "
                f"{a.shape} x {b.shape}")
        m = default_metrics()
        m.counter("repro.shard.executes").inc()
        if self.spec.collective_bytes:
            m.counter("repro.shard.collective_bytes").inc(
                self.spec.collective_bytes)
        if self.op is ConvOp.DGRAD:
            a, b = dgrad_operands(a, b)
        elif self.op is ConvOp.WGRAD:
            a, b = wgrad_operands(a, b)
        out = self._runner(a, b)
        if self.op is ConvOp.WGRAD:
            out = wgrad_finish(out[:self.out_hw[0], :self.out_hw[1]])
        return out

    __call__ = execute

    # -- the sharded executable (built once, cached on the frozen plan) ----
    @functools.cached_property
    def _mesh(self) -> Mesh:
        n = self.spec.n_shards
        return Mesh(np.asarray(self.devices).reshape(n, -1), ("shard", "rep"))

    @functools.cached_property
    def _runner(self):
        """Jitted global-array fprop-form executor for the exec scene."""
        spec, E, inner = self.spec, self.exec_scene, self.inner
        n, sub = spec.n_shards, spec.sub_scene
        if len(self.devices) == 1:
            return inner.execute
        mesh = self._mesh

        if n == 1:
            fn = _SHMAP(inner.execute, mesh=mesh, in_specs=(P(), P()),
                        out_specs=P())
        elif spec.axis == "batch":
            nb = n * sub.B

            def fn(a, b):
                out = _SHMAP(inner.execute, mesh=mesh,
                             in_specs=(P(None, None, None, "shard"), P()),
                             out_specs=P(None, None, None, "shard"))(
                                 _pad_axis(a, 3, nb), b)
                return out[..., :E.N]
        elif spec.axis == "oc":
            mp = n * sub.OC

            def fn(a, b):
                out = _SHMAP(inner.execute, mesh=mesh,
                             in_specs=(P(), P(None, None, None, "shard")),
                             out_specs=P(None, None, "shard", None))(
                                 a, _pad_axis(b, 3, mp))
                return out[:, :, :E.M, :]
        elif spec.axis == "ic":
            kp = n * sub.IC

            def body(a, b):
                return jax.lax.psum(inner.execute(a, b), "shard")

            def fn(a, b):
                return _SHMAP(body, mesh=mesh,
                              in_specs=(P(None, None, "shard"),
                                        P(None, None, "shard")),
                              out_specs=P())(
                                  _pad_axis(a, 2, kp), _pad_axis(b, 2, kp))
        elif spec.axis == "h":
            geo = halo_geometry(E, n)
            T = n * geo.ch
            perm = [((i + 1) % n, i) for i in range(n)]

            def body(chunk, tail, b):
                if geo.halo > 0:
                    idx = jax.lax.axis_index("shard")
                    parts, rot = [chunk], chunk
                    for k in range(1, geo.hops + 1):
                        # rotate chunks one shard down the ring; shards
                        # whose window ran past the partitioned extent take
                        # the replicated tail row block instead of the
                        # wrapped-around chunk
                        rot = jax.lax.ppermute(rot, "shard", perm=perm)
                        t_off = jnp.clip(idx + k - n, 0,
                                         max(geo.hops - 1, 0)) * geo.ch
                        tail_k = jax.lax.dynamic_slice_in_dim(
                            tail, t_off, geo.ch, axis=0)
                        parts.append(jnp.where((idx + k) >= n, tail_k, rot))
                    slab = jnp.concatenate(parts, axis=0)[:geo.slab]
                else:
                    slab = chunk[:geo.slab]
                return inner.execute(slab, b)

            def fn(a, b):
                # pre-pad the global input once (top padH + zeros out to the
                # last row any shard's window can touch); the sub-scene has
                # padH = 0, so shard-local windows never re-pad H.  The
                # slice after the pad handles scenes whose stride remainder
                # leaves real input rows no window reads.
                bot = max(0, geo.total - E.padH - E.inH)
                pin = jnp.pad(a, ((E.padH, bot), (0, 0), (0, 0),
                                  (0, 0)))[:geo.total]
                out = _SHMAP(body, mesh=mesh,
                             in_specs=(P("shard"), P(), P()),
                             out_specs=P("shard"))(pin[:T], pin[T:], b)
                return out[:E.outH]
        else:  # pragma: no cover — ShardSpec.__post_init__ forbids this
            raise ValueError(f"unknown partition axis {spec.axis!r}")
        return jax.jit(fn)

    # -- introspection -----------------------------------------------------
    def io_shapes(self) -> Tuple[Tuple[int, ...], Tuple[int, ...],
                                 Tuple[int, ...]]:
        """(arg-a shape, arg-b shape, result shape) of ``execute`` — global
        shapes, identical to the unsharded plan's."""
        names = _IO_SHAPES[self.op]
        return tuple(getattr(self.scene, nm)() for nm in names)

    @property
    def n_shards(self) -> int:
        return self.spec.n_shards

    @property
    def choice(self) -> ScheduleChoice:
        return self.spec.choice

    @property
    def schedule(self) -> str:
        return self.spec.choice.schedule

    @property
    def predicted_s(self) -> float:
        """Whole-dispatch model: per-shard schedule time + collective term
        + shard launch overhead (= ``spec.predicted_s``)."""
        return self.spec.predicted_s

    @property
    def shard_tag(self) -> str:
        """Partition fragment of the registry signature (``axis:n``)."""
        return self.spec.tag

    @property
    def uses_reference(self) -> bool:
        return self.inner.uses_reference

    @property
    def notes(self) -> Tuple[str, ...]:
        return self.inner.notes

    def describe(self) -> str:
        return (f"sharded-plan({self.op.value} {self.spec.tag} "
                f"{self.spec.choice.schedule} policy={self.policy} "
                f"coll={self.spec.collective_bytes}B "
                f"{self.scene.describe()})")


# --------------------------------------------------------------------------
# construction
# --------------------------------------------------------------------------
def _exec_scene_for(scene: ConvScene, op: ConvOp
                    ) -> Tuple[ConvScene, Tuple[int, int]]:
    """(exec scene, wgrad slice-back) of one op.  Raises ``ValueError`` for
    the ops with no MG3M exec scene (apad scenes, over-padded dgrad) — the
    sharded wrapper has no reference route; use ``make_plan`` there."""
    if op is ConvOp.FPROP:
        return scene, (0, 0)
    if op is ConvOp.DGRAD:
        return grad_input_scene(scene), (0, 0)
    return grad_filter_scene(scene), (scene.fltH, scene.fltW)


def _allowed_schedules(tag: str) -> Tuple[str, ...]:
    """Schedules the joint selector may use under a policy tag.  A forced
    grain ("forced:TB18") restricts the sub-scene selection the way it
    restricts unsharded selection; exact forced blockings
    ("forced:TB88@8/8/8") cannot transfer to a sub-scene whose dims the
    partition changed — refuse instead of silently re-blocking."""
    if not tag.startswith("forced:"):
        return SCHEDULES
    name = tag[len("forced:"):]
    if "@" in name:
        raise ValueError(
            f"policy {tag!r} pins exact blocks for the *unsharded* scene; "
            f"a sharded plan re-selects blocks for each sub-scene — force "
            f"the schedule alone (e.g. 'TB88') instead")
    return (name,)


def make_sharded_plan(scene: ConvScene, op: Union[ConvOp, str] = ConvOp.FPROP,
                      *, policy: PolicySpec = "analytic",
                      devices: Optional[Sequence] = None,
                      max_shards: Optional[int] = None,
                      axes: Sequence[str] = PARTITION_AXES,
                      model: Optional[CostModel] = None,
                      spec: Optional[ShardSpec] = None) -> ShardedConvPlan:
    """Build a frozen ``ShardedConvPlan``: derive the op's exec scene, pick
    (partition x grain) jointly (``select_shard_spec``), build the
    per-shard fprop-form plan with its choice pinned.

    ``devices`` is the device pool (default: all local devices);
    ``max_shards`` additionally caps it (default: the pool size).  The plan
    runs on the first multiple of ``n_shards`` devices of the capped pool —
    all of it for every count the selector proposes.
    ``axes`` restricts the candidate partitions — ``("batch",)`` is the
    serving layer's data-parallel mode.  ``spec`` pins a partition exactly
    (the registry's reload path and the tests' "force a partition" knob);
    it is re-validated against the exec scene, never trusted blindly.
    ``model=None`` uses the active (calibrated if an artifact exists) cost
    model, like unsharded plan building does.
    """
    op = ConvOp(op)
    tag = policy_tag(policy)
    if isinstance(policy, ScheduleChoice):
        raise ValueError(
            "make_sharded_plan cannot pin an exact ScheduleChoice: the "
            "joint selector re-blocks for each candidate sub-scene; force "
            "a schedule name, or pin a full ShardSpec via spec=")
    allowed = _allowed_schedules(tag)
    if model is None:
        model = _active_cost_model()
    devs = tuple(devices) if devices is not None else tuple(jax.devices())
    if not devs:
        raise ValueError("empty device pool")
    cap = len(devs) if max_shards is None else min(max_shards, len(devs))
    t0 = time.perf_counter()
    with default_tracer().span("repro.shard.make_plan", op=op.value,
                               policy=tag, scene=scene.describe()):
        exec_scene, out_hw = _exec_scene_for(scene, op)
        if spec is None:
            spec = select_shard_spec(exec_scene, max_shards=cap, axes=axes,
                                     allowed=allowed, model=model)
        else:
            _validate_spec(spec, exec_scene, len(devs))
        inner = make_plan(spec.sub_scene, ConvOp.FPROP, policy=spec.choice)
        m = default_metrics()
        m.counter("repro.shard.plans").inc()
        if not spec.is_sharded:
            m.counter("repro.shard.fallbacks").inc()
        m.histogram("repro.shard.plan_build_s").observe(
            time.perf_counter() - t0)
        n = spec.n_shards
        return ShardedConvPlan(scene=scene, op=op, policy=tag,
                               spec=spec, inner=inner,
                               exec_scene=exec_scene,
                               devices=devs[:max(cap - cap % n, n)],
                               out_hw=out_hw)


def _validate_spec(spec: ShardSpec, exec_scene: ConvScene,
                   n_devices: int) -> None:
    if spec.n_shards > n_devices:
        raise ValueError(
            f"spec wants {spec.n_shards} shards but only {n_devices} "
            f"device(s) are available")
    want = (exec_scene if not spec.is_sharded
            else shard_sub_scene(exec_scene, spec.axis, spec.n_shards))
    if spec.sub_scene != want:
        raise ValueError(
            f"pinned ShardSpec sub-scene {spec.sub_scene.describe()} does "
            f"not re-derive from {exec_scene.describe()} under "
            f"{spec.tag} (expected {want.describe()})")


def pinned_shard_spec(scene: ConvScene, op: Union[ConvOp, str], axis: str,
                      n_shards: int, choice: ScheduleChoice) -> ShardSpec:
    """Rebuild a ``ShardSpec`` from its persisted identity (axis, count,
    sub-scene choice) — cost terms are recomputed, the choice is pinned.
    The registry's deserialization path and the "force a partition" knob.
    """
    exec_scene, _ = _exec_scene_for(scene, ConvOp(op))
    if n_shards == 1:
        return ShardSpec(axis=UNSHARDED_AXIS, n_shards=1,
                         sub_scene=exec_scene, choice=choice,
                         predicted_s=choice.predicted_s,
                         collective_s=0.0, collective_bytes=0)
    sub = shard_sub_scene(exec_scene, axis, n_shards)
    coll_s = collective_seconds(exec_scene, axis, n_shards)
    return ShardSpec(
        axis=axis, n_shards=n_shards, sub_scene=sub, choice=choice,
        predicted_s=choice.predicted_s + coll_s + SHARD_LAUNCH_OVERHEAD_S,
        collective_s=coll_s,
        collective_bytes=collective_bytes(exec_scene, axis, n_shards))


def assemble_sharded_plan(scene: ConvScene, op: Union[ConvOp, str],
                          policy: str, axis: str, n_shards: int,
                          choice: ScheduleChoice, *,
                          devices: Optional[Sequence] = None
                          ) -> ShardedConvPlan:
    """Rebuild a sharded plan from stored identity without re-running the
    joint selector (the registry's artifact path).  Raises ``ValueError``
    when the process has fewer devices than the stored ring — the loader
    skips such entries the way it skips any stale plan."""
    spec = pinned_shard_spec(scene, op, axis, n_shards, choice)
    return make_sharded_plan(scene, op, policy=policy, devices=devices,
                             spec=spec)
