"""AdamW with global-norm clipping, built from scratch (no optax offline).

Moments are fp32 and mirror the parameter sharding specs (ZeRO-3: both are
fully sharded).  Params may be bf16; the update math runs fp32 and casts back
on write (DESIGN.md documents the no-fp32-master tradeoff and the memory
budget it buys at llama3-405b scale).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10000
    min_lr_frac: float = 0.1
    # "bfloat16" halves optimizer-state memory (405B: 12.6 -> 6.3 GB/chip);
    # the update math still runs fp32 (moments upcast per leaf).
    moments_dtype: str = "float32"


class OptState(NamedTuple):
    m: Any
    v: Any
    step: jax.Array


class TrainState(NamedTuple):
    params: Any
    opt: OptState


def init_opt_state(params: Any, moments_dtype: str = "float32") -> OptState:
    dt = jnp.dtype(moments_dtype)
    zeros = lambda p: jnp.zeros(p.shape, dt)
    return OptState(m=jax.tree.map(zeros, params),
                    v=jax.tree.map(zeros, params),
                    step=jnp.zeros((), jnp.int32))


def lr_schedule(cfg: AdamWConfig, step: jax.Array) -> jax.Array:
    """Linear warmup -> cosine decay to min_lr_frac."""
    step = step.astype(F32)
    warm = cfg.lr * step / max(cfg.warmup_steps, 1)
    prog = jnp.clip((step - cfg.warmup_steps)
                    / max(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0)
    cos = cfg.lr * (cfg.min_lr_frac + (1 - cfg.min_lr_frac)
                    * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
    return jnp.where(step < cfg.warmup_steps, warm, cos)


def global_norm(tree: Any) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(F32))) for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def clip_by_global_norm(grads: Any, max_norm: float):
    """Returns (scale, norm) — the scale is applied per-leaf inside the
    fused update so no full-size f32 gradient tree is ever materialized
    (at 405B that tree alone is 6.3 GB/chip)."""
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return scale, norm


def adamw_update(cfg: AdamWConfig, params: Any, grads: Any, state: OptState):
    """Returns (new_params, new_state, metrics).  All math fp32 per leaf;
    moments stored at cfg.moments_dtype."""
    scale, gnorm = clip_by_global_norm(grads, cfg.clip_norm)
    step = state.step + 1
    lr = lr_schedule(cfg, step)
    b1, b2 = cfg.beta1, cfg.beta2
    bc1 = 1 - b1 ** step.astype(F32)
    bc2 = 1 - b2 ** step.astype(F32)

    def upd(p, g, m, v):
        g32 = g.astype(F32) * scale
        m_new = b1 * m.astype(F32) + (1 - b1) * g32
        v_new = b2 * v.astype(F32) + (1 - b2) * jnp.square(g32)
        mhat = m_new / bc1
        vhat = v_new / bc2
        p32 = p.astype(F32)
        p32 = p32 - lr * (mhat / (jnp.sqrt(vhat) + cfg.eps)
                          + cfg.weight_decay * p32)
        return (p32.astype(p.dtype), m_new.astype(m.dtype),
                v_new.astype(v.dtype))

    flat_p, tdef = jax.tree.flatten(params)
    flat_g = tdef.flatten_up_to(grads)
    flat_m = tdef.flatten_up_to(state.m)
    flat_v = tdef.flatten_up_to(state.v)
    out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = tdef.unflatten([o[0] for o in out])
    new_m = tdef.unflatten([o[1] for o in out])
    new_v = tdef.unflatten([o[2] for o in out])
    return new_p, OptState(new_m, new_v, step), {"grad_norm": gnorm, "lr": lr}
