"""Depthwise causal conv1d Pallas kernel (the Mamba2 conv inside zamba2-7b).

A 1D instance of the MG3MConv idea: the scene (B, L, D, K) is small-filter
and memory-bound, so the selected granularity is always a TB11-style
schedule — the whole (tiny) filter stays resident in VMEM while the grid
streams (batch, L-blocks, D-blocks).  The causal left halo is provided by
passing the input twice with block index maps offset by one L-block
(a Pallas-friendly encoding of overlapping windows).

Layouts: x [B, L, D], w [K, D], y [B, L, D] with
  y[b, l, d] = sum_k w[k, d] * x[b, l - (K-1) + k, d].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.scene import round_up
from repro.kernels import interpret_mode


def _kernel(x_ref, prev_ref, w_ref, out_ref, *, kw: int, block_l: int):
    li = pl.program_id(1)
    x = x_ref[0].astype(jnp.float32)            # (block_l, bd)
    prev = prev_ref[0].astype(jnp.float32)      # (block_l, bd)
    # First L-block has no real predecessor: its halo is zeros.
    prev = jnp.where(li == 0, jnp.zeros_like(prev), prev)
    acc = x * w_ref[kw - 1].astype(jnp.float32)[None, :]
    for k in range(1, kw):                      # static unroll: K is tiny (<=4)
        shifted = jnp.concatenate([prev[block_l - k:], x[:block_l - k]], axis=0)
        acc += shifted * w_ref[kw - 1 - k].astype(jnp.float32)[None, :]
    out_ref[0] = acc.astype(out_ref.dtype)


def causal_conv1d(x: jax.Array, w: jax.Array, *, block_l: int,
                  block_d: int) -> jax.Array:
    b, l, d = x.shape
    kw = w.shape[0]
    if l % block_l != 0 or d % block_d != 0:
        raise ValueError(
            f"(L={l}, D={d}) not divisible by blocks "
            f"(block_l={block_l}, block_d={block_d})")
    if kw > block_l:
        raise ValueError(f"filter width {kw} longer than an L block "
                         f"{block_l}")
    grid = (b, l // block_l, d // block_d)
    kernel = functools.partial(_kernel, kw=kw, block_l=block_l)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_l, block_d), lambda bi, li, di: (bi, li, di)),
            # The same array, one L-block to the left (clamped; masked in-kernel).
            pl.BlockSpec((1, block_l, block_d),
                         lambda bi, li, di: (bi, jnp.maximum(li - 1, 0), di)),
            pl.BlockSpec((kw, block_d), lambda bi, li, di: (0, di)),
        ],
        out_specs=pl.BlockSpec((1, block_l, block_d),
                               lambda bi, li, di: (bi, li, di)),
        out_shape=jax.ShapeDtypeStruct((b, l, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "parallel")),
        interpret=interpret_mode(),
    )(x, x, w)


def causal_conv1d_op(x: jax.Array, w: jax.Array, *, block_l: int = 256,
                     block_d: int = 256) -> jax.Array:
    """Depthwise causal conv1d (Mamba2's conv) at any (L, D): pads both to
    whole blocks, runs ``causal_conv1d`` and slices the padding off."""
    b, l, d = x.shape
    bl = min(block_l, l)
    bd = min(block_d, d)
    lp, dp = round_up(l, bl), round_up(d, bd)
    x_a = jnp.pad(x, ((0, 0), (0, lp - l), (0, dp - d)))
    w_a = jnp.pad(w, ((0, 0), (0, dp - d)))
    out = causal_conv1d(x_a, w_a, block_l=bl, block_d=bd)
    return out[:, :l, :d]
