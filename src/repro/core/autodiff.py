"""Differentiable MG3MConv: custom_vjp built from ``repro.plan`` plans.

All three directions are first-class plan ops (``ConvOp.FPROP`` /
``DGRAD`` / ``WGRAD``): the backward convolutions are themselves MG3M
*scenes* whose granularity the selector picks independently of the forward
(dOUT has OC channels where IN had IC; wgrad contracts the batch dim).
Scene derivation lives in ``repro.plan.build`` (``grad_input_scene`` /
``grad_filter_scene``); strided forwards dispatch to Pallas in all three
directions (the backward scenes are dilated).  ``uses_reference`` is
recorded *per op*: the rare genuinely-inexpressible direction (padding
exceeding the dilated filter extent minus one blocks dgrad only) falls
back alone while the other two still run Pallas — see
``TrainingPlans.reference_ops``.

Two APIs, by scope:

  * ``make_training_plans`` + ``conv_with_plans``: plan-once / execute-many —
    build the (fprop, dgrad, wgrad) triple per layer, then every training
    step is pure dispatch (what ``models/cnn.py`` and the examples use);
  * ``make_model_plans`` + ``apply_conv``: the whole-CNN unit — one
    ``ModelPlans`` holds every layer's triple, prewarmed through
    ``PlanRegistry.warm`` (or built as mesh-sharded triples via
    ``repro.shard.autodiff`` when ``devices`` are given), so an entire
    training step touches zero schedule resolutions (``repro.train.cnn``
    builds its step functions on this).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Iterator, Mapping, Optional, Sequence, Tuple, Union

import jax

from repro.core.mapping import ScheduleChoice
from repro.core.scene import ConvScene
from repro.plan.build import ConvOp, ConvPlan, make_plan
from repro.plan.registry import PlanRegistry, default_registry


@dataclasses.dataclass(frozen=True)
class TrainingPlans:
    """The (fprop, dgrad, wgrad) plan triple of one trainable conv layer."""

    fprop: ConvPlan
    dgrad: ConvPlan
    wgrad: ConvPlan

    @property
    def scene(self) -> ConvScene:
        return self.fprop.scene

    @property
    def uses_reference(self) -> bool:
        """True when *any* direction bypasses Pallas — an aggregate.  The
        per-op truth is ``reference_ops``: a blocked dgrad does not stop
        fprop/wgrad from dispatching to Pallas, so don't branch a whole
        layer to reference on this alone."""
        return bool(self.reference_ops)

    @property
    def reference_ops(self) -> tuple:
        """Names of the directions that execute the jnp reference (each
        plan's ``uses_reference`` recorded per op), e.g. ``("dgrad",)``."""
        return tuple(p.op.value for p in (self.fprop, self.dgrad, self.wgrad)
                     if p.uses_reference)

    def describe(self) -> str:
        return " | ".join(p.describe() for p in (self.fprop, self.dgrad,
                                                 self.wgrad))


def backward_policy(policy: Union[None, str, ScheduleChoice]) -> str:
    """Policy the backward directions resolve under for a given fprop policy:
    "tuned" follows fprop into the schedule cache (the backward scenes get
    their own entries); everything else — analytic *and* forced — selects
    analytically, because a grain forced for the forward is not forced on
    the backward scenes, whose best grain generally differs."""
    return "tuned" if policy in ("auto", "tuned") else "analytic"


def make_training_plans(scene: ConvScene, *,
                        policy: Union[None, str, ScheduleChoice] = "analytic",
                        registry: Optional[PlanRegistry] = None
                        ) -> TrainingPlans:
    """Plan all three directions of one layer, each through the selector.

    ``policy`` applies to fprop; the backward plans resolve under
    ``backward_policy(policy)`` (see there for why forced grains don't
    propagate to the backward scenes).
    """
    bwd_policy = backward_policy(policy)
    build = functools.partial(
        registry.get_or_build if registry is not None else make_plan, scene)
    return TrainingPlans(fprop=build(ConvOp.FPROP, policy=policy),
                         dgrad=build(ConvOp.DGRAD, policy=bwd_policy),
                         wgrad=build(ConvOp.WGRAD, policy=bwd_policy))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def conv_with_plans(inp: jax.Array, flt: jax.Array,
                    plans: TrainingPlans) -> jax.Array:
    """Differentiable convolution over a pre-built plan triple: every
    direction is a zero-resolution dispatch."""
    return plans.fprop.execute(inp, flt)


def _fwd(inp, flt, plans):
    return conv_with_plans(inp, flt, plans), (inp, flt)


def _bwd(plans, residuals, d_out):
    inp, flt = residuals
    return plans.dgrad.execute(d_out, flt), plans.wgrad.execute(inp, d_out)


conv_with_plans.defvjp(_fwd, _bwd)


# --------------------------------------------------------------------------
# whole-model plans
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ModelPlans:
    """Per-layer (fprop, dgrad, wgrad) plan triples for a whole CNN.

    The plan-once unit of ``repro.train.cnn``: build every layer's triple
    before the first step (``make_model_plans`` prewarms them through one
    ``PlanRegistry.warm`` call), then the training step is pure dispatch
    end to end.  A layer slot holds either a ``TrainingPlans`` or — when
    the model was built for a device ring — a
    ``repro.shard.autodiff.ShardedTrainingPlans``; ``apply_conv``
    dispatches both.  Frozen and hashable, so a step function can close
    over it (or take it as a static argument) under ``jax.jit``.
    """

    layers: Tuple[Tuple[str, object], ...]   # (name, plan triple), in order

    def __getitem__(self, name: str):
        for n, triple in self.layers:
            if n == name:
                return triple
        raise KeyError(name)

    def __iter__(self) -> Iterator[str]:
        return (n for n, _ in self.layers)

    def __len__(self) -> int:
        return len(self.layers)

    def __contains__(self, name: str) -> bool:
        return any(n == name for n, _ in self.layers)

    def names(self) -> Tuple[str, ...]:
        return tuple(n for n, _ in self.layers)

    def items(self) -> Tuple[Tuple[str, object], ...]:
        return self.layers

    def scenes(self) -> Dict[str, ConvScene]:
        """The forward scene of every layer, in layer order."""
        return {n: triple.scene for n, triple in self.layers}

    @property
    def reference_ops(self) -> Dict[str, Tuple[str, ...]]:
        """``{layer: (op, ...)}`` for layers where any direction executes
        the jnp reference — empty dict when the whole model is Pallas."""
        out = {}
        for n, triple in self.layers:
            ops = triple.reference_ops
            if ops:
                out[n] = ops
        return out

    def plans(self) -> Iterator[Tuple[str, str, object]]:
        """Flat (layer, op, plan) walk over every direction of every layer
        — what benchmarks and the drift feed iterate."""
        for n, triple in self.layers:
            for p in (triple.fprop, triple.dgrad, triple.wgrad):
                yield n, p.op.value, p

    def describe(self) -> str:
        return "\n".join(f"{n}: {triple.describe()}"
                         for n, triple in self.layers)


def make_model_plans(scenes: Mapping[str, ConvScene], *,
                     policy: Union[None, str, ScheduleChoice] = "analytic",
                     registry: Optional[PlanRegistry] = None,
                     devices: Optional[Sequence] = None,
                     max_shards: Optional[int] = None) -> ModelPlans:
    """Plan a whole CNN: one (fprop, dgrad, wgrad) triple per layer.

    In-process (``devices=None``): every (scene x op) plan is prewarmed
    through ``registry.warm`` — one locked pass that builds whatever is
    missing without inflating hit/miss traffic stats — and the triples
    then assemble from pure registry hits, so "zero resolutions after
    warmup" is assertable from the ``repro.plan.resolutions`` counter.

    With ``devices`` (a data-parallel ring, e.g.
    ``launch.mesh.data_devices(mesh)``): each layer builds mesh-sharded
    triples via ``repro.shard.autodiff.make_sharded_training_plans``,
    whose joint (partition x grain) selector falls back to ``n_shards=1``
    per direction whenever partitioning is a predicted loss.
    """
    if devices is not None:
        from repro.shard.autodiff import make_sharded_training_plans
        return ModelPlans(layers=tuple(
            (name, make_sharded_training_plans(
                sc, policy=policy if isinstance(policy, str) else "analytic",
                devices=devices, max_shards=max_shards))
            for name, sc in scenes.items()))
    reg = registry if registry is not None else default_registry()
    scene_list = list(scenes.values())
    bwd = backward_policy(policy)
    reg.warm(scene_list, ops=(ConvOp.FPROP,), policy=policy)
    reg.warm(scene_list, ops=(ConvOp.DGRAD, ConvOp.WGRAD), policy=bwd)
    return ModelPlans(layers=tuple(
        (name, make_training_plans(sc, policy=policy, registry=reg))
        for name, sc in scenes.items()))


def apply_conv(inp: jax.Array, flt: jax.Array, plans) -> jax.Array:
    """Differentiable dispatch for either plan flavour of one layer —
    operands in plan layout (IN[H,W,C,B], FLT[h,w,IC,OC]).  The one entry
    model forwards call, so a model built sharded and one built in-process
    share the same forward code."""
    if isinstance(plans, TrainingPlans):
        return conv_with_plans(inp, flt, plans)
    from repro.shard.autodiff import (ShardedTrainingPlans,
                                      sharded_conv_with_plans)
    if isinstance(plans, ShardedTrainingPlans):
        return sharded_conv_with_plans(inp, flt, plans)
    raise ValueError(
        f"apply_conv expects a TrainingPlans or ShardedTrainingPlans, "
        f"got {type(plans).__name__}")

