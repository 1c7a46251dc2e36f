"""Distributed train / serve steps.

Train: gradient accumulation over microbatches (lax.scan), per-layer remat
inside the model, AdamW update — all under one jit with explicit
in/out_shardings.  Activation residuals are sequence-sharded over 'model'
(Megatron-SP) via the ctx hooks, which is what makes llama3-405b train_4k fit
the 16 GB/chip budget (DESIGN.md §6).

Serve: prefill (returns last-position logits + cache) and single-token decode.

Optional distributed-optimization tricks:
  * bf16 gradient-compression accumulation (`grad_compression="bf16"`):
    microbatch grads are accumulated/communicated in bf16, halving gradient
    all-reduce bytes; final update math stays fp32.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, SHAPES
from repro.models import transformer as T
from repro.parallel import ctx, sharding
from repro.train import optimizer as opt
from repro.train.optimizer import TrainState

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class StepPlan:
    """Per-(arch x shape) execution plan — the runtime knobs."""
    n_microbatches: int = 1
    grad_compression: Optional[str] = None   # None | "bf16"
    seq_shard_activations: bool = True
    skip_update: bool = False                # roofline probes: grads only
    tp: bool = True                          # False = small-scene DP grain


def default_plan(cfg: ArchConfig, shape_name: str, mesh) -> StepPlan:
    """The multi-grained *cluster* mapping decision (paper Fig. 14 analogue):
    small-d_model trains use the DP grain (tp=False: 'model' axis joins the
    batch axes, no TP/SP all-gathers); big models use TP-16 + SP.  Microbatch
    count sized so the per-shard microbatch stays small at big d_model."""
    kind = SHAPES[shape_name]["kind"]
    tp = not (kind == "train" and cfg.d_model < 4096)
    b = SHAPES[shape_name]["global_batch"]
    dp = sharding.dp_size(mesh) * (1 if tp else
                                   sharding.model_axis_size(mesh))
    # 2 samples/shard at big d_model: halves the number of microbatches and
    # with it the per-step FSDP parameter re-gathers (§Perf iter 4)
    per_shard_target = 2 if cfg.d_model >= 6144 else 4
    n_mb = max(1, b // max(dp * per_shard_target, 1))
    # keep microbatches a divisor of the global batch
    while b % n_mb:
        n_mb -= 1
    # bf16 gradient-compression accumulation at scale: halves both the
    # accumulator footprint and gradient-reduction bytes
    compress = "bf16" if cfg.param_count() >= 30e9 else None
    return StepPlan(n_microbatches=n_mb, grad_compression=compress, tp=tp)


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Vocab-parallel-safe CE: mask+sum instead of take_along_axis so a
    vocab-sharded logits tensor never gets all-gathered."""
    logits = logits.astype(F32)
    lse = jax.nn.logsumexp(logits, -1)
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    picked = jnp.sum(jnp.where(iota == labels[..., None], logits, 0.0), -1)
    return (lse - picked).mean()


def loss_fn(params, cfg: ArchConfig, batch) -> Tuple[jax.Array, Dict]:
    logits, aux = T.forward(params, cfg, tokens=batch.get("tokens"),
                            embeds=batch.get("embeds"))
    ce = cross_entropy(logits, batch["labels"])
    total = ce + T.AUX_LOSS_WEIGHT * aux
    return total, {"ce_loss": ce, "moe_aux": aux}


# ---------------------------------------------------------------------------
# Train step builder
# ---------------------------------------------------------------------------
def build_train_step(cfg: ArchConfig, mesh, opt_cfg: opt.AdamWConfig,
                     plan: StepPlan):
    """Returns (train_step_fn, hooks) — call under `with mesh:` and the
    activation_sharding(hooks) context (or use `lower_train_step`)."""
    dp = sharding.dp_axes(mesh)
    hooks = ctx.residual_hooks(mesh, dp, plan.seq_shard_activations, plan.tp)

    def train_step(state: TrainState, batch):
        n_mb = plan.n_microbatches
        acc_dtype = jnp.bfloat16 if plan.grad_compression == "bf16" else F32

        def one_microbatch(params, mb):
            (loss, stats), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, cfg, mb)
            return loss, stats, grads

        if n_mb == 1:
            loss, stats, grads = one_microbatch(state.params, batch)
        else:
            def reshape_mb(x):
                x = x.reshape(n_mb, x.shape[0] // n_mb, *x.shape[1:])
                return x
            mbs = jax.tree.map(reshape_mb, batch)

            def acc_body(carry, mb):
                g_acc, l_acc = carry
                loss, stats, grads = one_microbatch(state.params, mb)
                g_acc = jax.tree.map(
                    lambda a, g: a + g.astype(acc_dtype), g_acc, grads)
                return (g_acc, l_acc + loss), stats

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, acc_dtype),
                              state.params)
            (g_acc, l_acc), stats = jax.lax.scan(acc_body, (g0, 0.0), mbs)
            grads = jax.tree.map(lambda g: g / n_mb, g_acc)
            loss = l_acc / n_mb
            stats = jax.tree.map(lambda s: s.mean(), stats)

        if plan.skip_update:
            # roofline probe: emit grads as sharded outputs so GSPMD
            # reduce-scatters them exactly like the accumulation step does
            return state, {"loss": loss, "grads": grads}
        new_params, new_opt, metrics = opt.adamw_update(
            opt_cfg, state.params, grads, state.opt)
        metrics = dict(metrics, loss=loss, **stats)
        return TrainState(new_params, new_opt), metrics

    return train_step, hooks


def state_pspecs(cfg: ArchConfig, state_shapes: TrainState, mesh,
                 tp: bool = True):
    pspec = sharding.param_pspecs(cfg, state_shapes.params, mesh, tp)
    mspec = sharding.param_pspecs(cfg, state_shapes.opt.m, mesh, tp)
    return TrainState(params=pspec,
                      opt=opt.OptState(m=mspec, v=mspec, step=P()))


def jit_train_step(cfg: ArchConfig, shape_name: str, mesh, plan: StepPlan,
                   opt_cfg: opt.AdamWConfig, state_like: TrainState):
    """Jit the train step with explicit in/out shardings derived from
    ``state_pspecs``/``batch_pspecs`` and the state buffers donated — the
    single construction both the launcher (which executes it) and
    ``lower_train_step`` (which lowers it for a dry-run cell) share, so
    what the dry run inspects is byte-for-byte what production runs.

    ``state_like`` may be concrete arrays or ``jax.eval_shape`` structs.
    Trace/call under ``with mesh:`` and ``ctx.activation_sharding(hooks)``.
    Returns (jitted_step, hooks, sspec).
    """
    step_fn, hooks = build_train_step(cfg, mesh, opt_cfg, plan)
    state_shape = jax.eval_shape(lambda s: s, state_like)
    sspec = state_pspecs(cfg, state_shape, mesh, plan.tp)
    bspec = sharding.batch_pspecs(cfg, shape_name, mesh, plan.tp)
    metrics_shardings = None
    if plan.skip_update:  # grads output must carry the param shardings
        metrics_shardings = {"loss": None,
                             "grads": sharding.named(mesh, sspec.params)}
    jitted = jax.jit(
        step_fn,
        in_shardings=(sharding.named(mesh, sspec),
                      sharding.named(mesh, bspec)),
        out_shardings=(sharding.named(mesh, sspec), metrics_shardings),
        donate_argnums=(0,),
    )
    return jitted, hooks, sspec


def lower_train_step(cfg: ArchConfig, shape_name: str, mesh,
                     plan: Optional[StepPlan] = None,
                     opt_cfg: Optional[opt.AdamWConfig] = None,
                     batch_override: Optional[int] = None):
    """Lower (no compile) the train step for one dry-run cell: abstract
    params/opt-state, explicit in/out shardings, state buffers donated."""
    from repro.configs.base import input_specs
    plan = plan or default_plan(cfg, shape_name, mesh)
    if opt_cfg is None:
        moments = "bfloat16" if cfg.param_count() >= 30e9 else "float32"
        opt_cfg = opt.AdamWConfig(moments_dtype=moments)

    params_shape = jax.eval_shape(
        functools.partial(T.init_params, cfg), jax.random.PRNGKey(0))
    state_shape = TrainState(params_shape,
                             jax.eval_shape(functools.partial(
                                 opt.init_opt_state,
                                 moments_dtype=opt_cfg.moments_dtype),
                                 params_shape))
    batch_shape = input_specs(cfg, shape_name, batch_override)
    jitted, hooks, _ = jit_train_step(cfg, shape_name, mesh, plan, opt_cfg,
                                      state_shape)
    with mesh:
        with ctx.activation_sharding(hooks):
            lowered = jitted.lower(state_shape, batch_shape)
    return lowered


# ---------------------------------------------------------------------------
# Serve steps
# ---------------------------------------------------------------------------
def build_prefill_step(cfg: ArchConfig, mesh, plan: StepPlan):
    hooks = ctx.residual_hooks(mesh, sharding.dp_axes(mesh),
                               plan.seq_shard_activations)

    def prefill_step(params, batch):
        logits, cache = T.prefill(params, cfg, tokens=batch.get("tokens"),
                                  embeds=batch.get("embeds"))
        return logits[:, -1], cache

    return prefill_step, hooks


def build_decode_step(cfg: ArchConfig, mesh, plan: StepPlan):
    hooks = ctx.residual_hooks(mesh, sharding.dp_axes(mesh),
                               plan.seq_shard_activations)

    def decode_step(params, cache, batch):
        logits, new_cache = T.decode_step(
            params, cfg, cache, batch["position"],
            tokens=batch.get("tokens"), embeds=batch.get("embeds"))
        return logits[:, -1], new_cache

    return decode_step, hooks


def lower_serve_step(cfg: ArchConfig, shape_name: str, mesh,
                     plan: Optional[StepPlan] = None):
    """Lower prefill or decode for one dry-run cell."""
    from repro.configs.base import input_specs
    plan = plan or StepPlan(n_microbatches=1)
    kind = SHAPES[shape_name]["kind"]
    seq = SHAPES[shape_name]["seq_len"]
    bsz = SHAPES[shape_name]["global_batch"]

    params_shape = jax.eval_shape(
        functools.partial(T.init_params, cfg), jax.random.PRNGKey(0))
    pspec = sharding.param_pspecs(cfg, params_shape, mesh)
    bspec = sharding.batch_pspecs(cfg, shape_name, mesh)
    batch_shape = input_specs(cfg, shape_name)

    if kind == "prefill":
        fn, hooks = build_prefill_step(cfg, mesh, plan)
        cspec = sharding.cache_pspecs(cfg, shape_name, mesh)
        with ctx.activation_sharding({}):
            _, cache_shape = jax.eval_shape(fn, params_shape, batch_shape)
        cspec = sharding.sanitize_pspecs(cspec, cache_shape, mesh)
        with mesh:
            with ctx.activation_sharding(hooks):
                jitted = jax.jit(
                    fn,
                    in_shardings=(sharding.named(mesh, pspec),
                                  sharding.named(mesh, bspec)),
                    out_shardings=(None, sharding.named(mesh, cspec)),
                )
                lowered = jitted.lower(params_shape, batch_shape)
        return lowered

    if kind != "decode":
        raise ValueError(f"unknown serve step kind {kind!r}")
    fn, hooks = build_decode_step(cfg, mesh, plan)
    cache_shape = jax.eval_shape(
        functools.partial(T.init_cache, cfg, bsz, seq))
    cspec = sharding.cache_pspecs(cfg, shape_name, mesh)
    cspec = sharding.sanitize_pspecs(cspec, cache_shape, mesh)
    with mesh:
        with ctx.activation_sharding(hooks):
            jitted = jax.jit(
                fn,
                in_shardings=(sharding.named(mesh, pspec),
                              sharding.named(mesh, cspec),
                              sharding.named(mesh, bspec)),
                out_shardings=(None, sharding.named(mesh, cspec)),
                donate_argnums=(1,),
            )
            lowered = jitted.lower(params_shape, cache_shape, batch_shape)
    return lowered
