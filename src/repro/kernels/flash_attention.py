"""Flash-attention Pallas TPU kernel (fwd) with GQA-aware BlockSpecs.

The framework's hottest non-conv op, built with the same discipline as the
MG3MConv kernels: explicit VMEM tiling, fp32 running-softmax state in
persistent scratch, the KV reduction as the innermost grid dimension so the
output block is revisited (the paper's Alg. 2/3 accumulate-in-LDM pattern),
and Mosaic's automatic cross-step pipelining standing in for the paper's
double buffering.

GQA: the KV BlockSpec index map folds the query-head -> kv-head mapping
(h // group), so repeated KV heads are never materialized.

Layouts: q (BH, S, D), k/v (BHkv, T, D) — the ops.py wrapper reshapes from
the model's (B, S, H, D).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

F32 = jnp.float32
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, bq: int, bk: int, nk: int,
            out_dtype):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = True
    if causal:
        # whole block strictly above the diagonal: skip compute (the fetch
        # still pipelines; skipping it too is a BlockSpec-level follow-up)
        run = ik * bk <= iq * bq + bq - 1

    @pl.when(run if causal else True)
    def _step():
        q = q_ref[0].astype(F32)                   # (bq, D)
        k = k_ref[0].astype(F32)                   # (bk, D)
        v = v_ref[0].astype(F32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32) * scale
        if causal:
            q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=F32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(out_dtype)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, block_q: int = 128,
                        block_k: int = 128) -> jax.Array:
    """q: (BH, S, D); k, v: (BHkv, T, D); BH % BHkv == 0."""
    bh, s, d = q.shape
    bhkv, t, _ = k.shape
    if bh % bhkv != 0:
        raise ValueError(f"BH {bh} not a multiple of BHkv {bhkv}")
    g = bh // bhkv
    bq = min(block_q, s)
    bk = min(block_k, t)
    if s % bq != 0 or t % bk != 0:
        raise ValueError(f"(S={s}, T={t}) not divisible by blocks "
                         f"(bq={bq}, bk={bk})")
    nq, nk = s // bq, t // bk
    grid = (bh, nq, nk)
    kernel = functools.partial(
        _kernel, scale=d ** -0.5, causal=causal, bq=bq, bk=bk, nk=nk,
        out_dtype=q.dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, d), lambda h, i, j: (h // g, j, 0)),
            pl.BlockSpec((1, bk, d), lambda h, i, j: (h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq,), F32), pltpu.VMEM((bq,), F32),
                        pltpu.VMEM((bq, d), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_mode(),
    )(q, k, v)


def flash_attention_bshd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True, block_q: int = 128,
                         block_k: int = 128) -> jax.Array:
    """Model-layout wrapper: q (B,S,H,D), k/v (B,T,Hkv,D) -> (B,S,H,D)."""
    b, s, h, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, t, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, t, d)
    of = flash_attention_fwd(qf, kf, vf, causal=causal, block_q=block_q,
                             block_k=block_k)
    return of.reshape(b, h, s, d).transpose(0, 2, 1, 3)
