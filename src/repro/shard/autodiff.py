"""Differentiable mesh-sharded MG3MConv: custom_vjp over sharded plans.

Mirror of ``repro.core.autodiff`` with ``ShardedConvPlan`` in every slot:
the backward convolutions are themselves sharded dispatches, each with its
own jointly-selected (partition x grain), because the backward exec scenes
have different M/N/K and therefore different best partitions (dgrad swaps
IC/OC; wgrad contracts batch, so a "batch" partition of the *forward*
corresponds to an "ic" reduction partition of the wgrad exec scene — the
joint selector discovers that, nobody hand-maps it).

The rare direction with no MG3M exec scene (apad scenes block both
backwards; over-padded forwards block dgrad) falls back to the *unsharded*
reference plan for that direction alone — a sharded wrapper around a jnp
reference conv would shard nothing worth sharding.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple, Union

import jax

from repro.core.mapping import CostModel
from repro.core.scene import ConvScene
from repro.plan.build import ConvOp, ConvPlan, make_plan
from repro.shard.plan import ShardedConvPlan, make_sharded_plan
from repro.shard.spec import PARTITION_AXES

#: either flavour of plan — both expose execute(a, b) on global arrays
AnyPlan = Union[ShardedConvPlan, ConvPlan]


@dataclasses.dataclass(frozen=True)
class ShardedTrainingPlans:
    """The (fprop, dgrad, wgrad) triple of one mesh-sharded conv layer.

    ``fprop`` is always sharded (possibly the ``n_shards == 1`` fallback);
    a backward slot holds a plain unsharded ``ConvPlan`` only when its
    direction has no MG3M exec scene at all (see ``reference_ops``).
    """

    fprop: ShardedConvPlan
    dgrad: AnyPlan
    wgrad: AnyPlan

    @property
    def scene(self) -> ConvScene:
        return self.fprop.scene

    @property
    def reference_ops(self) -> Tuple[str, ...]:
        return tuple(p.op.value for p in (self.fprop, self.dgrad, self.wgrad)
                     if p.uses_reference)

    @property
    def shard_tags(self) -> Tuple[str, ...]:
        """Per-direction partition tags, "-" for unsharded fallbacks."""
        return tuple(getattr(p, "shard_tag", None) or "-"
                     for p in (self.fprop, self.dgrad, self.wgrad))

    def describe(self) -> str:
        return " | ".join(p.describe() for p in (self.fprop, self.dgrad,
                                                 self.wgrad))


def make_sharded_training_plans(scene: ConvScene, *, policy: str = "analytic",
                                devices: Optional[Sequence] = None,
                                max_shards: Optional[int] = None,
                                axes: Sequence[str] = PARTITION_AXES,
                                model: Optional[CostModel] = None
                                ) -> ShardedTrainingPlans:
    """Jointly select (partition x grain) for all three directions.

    Each direction runs the selector on its *own* exec scene, so the three
    plans may land on three different partition axes (or fall back to
    ``n_shards == 1`` independently).  Directions whose exec scene doesn't
    exist (``grad_*_scene`` raises) get the unsharded plan's reference
    route instead.
    """
    kw = dict(policy=policy, devices=devices, max_shards=max_shards,
              axes=axes, model=model)

    def build(op: ConvOp) -> AnyPlan:
        try:
            return make_sharded_plan(scene, op, **kw)
        except ValueError:
            # no MG3M exec scene for this direction: unsharded fallback
            # (make_plan routes it to the jnp reference and records why)
            return make_plan(scene, op, policy="analytic")

    return ShardedTrainingPlans(
        fprop=make_sharded_plan(scene, ConvOp.FPROP, **kw),
        dgrad=build(ConvOp.DGRAD),
        wgrad=build(ConvOp.WGRAD))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def sharded_conv_with_plans(inp: jax.Array, flt: jax.Array,
                            plans: ShardedTrainingPlans) -> jax.Array:
    """Differentiable convolution over a pre-built sharded plan triple:
    forward and both backwards are zero-resolution sharded dispatches."""
    return plans.fprop.execute(inp, flt)


def _fwd(inp, flt, plans):
    return sharded_conv_with_plans(inp, flt, plans), (inp, flt)


def _bwd(plans, residuals, d_out):
    inp, flt = residuals
    return plans.dgrad.execute(d_out, flt), plans.wgrad.execute(inp, d_out)


sharded_conv_with_plans.defvjp(_fwd, _bwd)
