"""Mamba2 (SSD) block — chunked matmul formulation (arXiv:2405.21060 §6).

The chunked form turns the selective-scan recurrence into MXU-friendly
matmuls: intra-chunk "attention-like" scores + an inter-chunk state
recurrence over L/chunk steps (a cheap lax.scan).  The depthwise causal
conv inside the block routes through the MG3MConv-style Pallas kernel
(kernels/causal_conv1d.py) when `use_pallas` is on; the pure-jnp path is
used under pjit for CPU dry-runs.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import SSMConfig
from repro.kernels.causal_conv1d import causal_conv1d_op
from repro.kernels import ref as kref
from repro.models.layers import trunc_normal

F32 = jnp.float32
Params = Dict[str, jax.Array]


def init_mamba2(key, d_model: int, cfg: SSMConfig, dtype, n_layers: int = 1
                ) -> Params:
    di = cfg.expand * d_model
    nh = di // cfg.head_dim
    conv_dim = di + 2 * cfg.n_groups * cfg.state
    ks = jax.random.split(key, 6)
    std = d_model ** -0.5
    proj_out = 2 * di + 2 * cfg.n_groups * cfg.state + nh
    p = {
        "in_proj": trunc_normal(ks[0], (d_model, proj_out), std, dtype),
        "conv_w": trunc_normal(ks[1], (cfg.conv_kernel, conv_dim), 0.2, dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh).astype(F32)),
        "D": jnp.ones((nh,), F32),
        "dt_bias": jnp.log(jnp.expm1(
            jnp.exp(jax.random.uniform(ks[2], (nh,), F32,
                                       math.log(1e-3), math.log(1e-1))))),
        "norm_scale": jnp.ones((di,), dtype),
        "out_proj": trunc_normal(ks[3], (di, d_model),
                                 (di ** -0.5) / math.sqrt(2 * n_layers), dtype),
    }
    return p


def _segsum_decay(a: jax.Array) -> jax.Array:
    """a: (..., Q) log-decays -> (..., Q, Q) lower-tri exp(segment sums).

    out[i, j] = exp(sum_{t=j+1..i} a_t) for i >= j, else 0.
    """
    q = a.shape[-1]
    cum = jnp.cumsum(a, -1)
    seg = cum[..., :, None] - cum[..., None, :]
    mask = jnp.tril(jnp.ones((q, q), bool))
    return jnp.where(mask, jnp.exp(seg), 0.0)


def ssd_chunked(x: jax.Array, dt: jax.Array, a_head: jax.Array, b: jax.Array,
                c: jax.Array, chunk: int) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD.
    x: (B, L, H, P); dt: (B, L, H) fp32 (post-softplus); a_head: (H,) negative;
    b, c: (B, L, G, S) with H % G == 0.
    Returns (y (B, L, H, P), final state (B, H, S, P)).
    """
    bs, l, h, p = x.shape
    g, s = b.shape[2], b.shape[3]
    if l % chunk != 0:
        raise ValueError(f"L {l} not divisible by chunk {chunk}")
    nc = l // chunk
    hg = h // g

    # Big tensors (inputs, B/C, scores) stay in the IO dtype — bf16 at scale
    # halves the SSD HBM traffic (§Perf zamba2 iter); decays/cumsums stay f32.
    io_dt = x.dtype
    xdt = (x.astype(F32) * dt[..., None]).astype(io_dt)      # discretized input
    la = dt * a_head[None, None, :]                          # (B, L, H) log decay
    # reshape into chunks
    xdt = xdt.reshape(bs, nc, chunk, h, p)
    la = la.reshape(bs, nc, chunk, h)
    bb = b.astype(io_dt).reshape(bs, nc, chunk, g, s)
    cc = c.astype(io_dt).reshape(bs, nc, chunk, g, s)

    cum = jnp.cumsum(la, 2)                                  # (B, nc, Q, H)
    lmat = _segsum_decay(jnp.moveaxis(la, -1, 2))            # (B, nc, H, Q, Q)

    # intra-chunk: scores[i,j] = (C_i . B_j) * decay(i,j)
    cb = jnp.einsum("bnigs,bnjgs->bngij", cc, bb,
                    preferred_element_type=F32)              # (B,nc,G,Q,Q)
    cb = jnp.repeat(cb, hg, axis=2) if g > 1 else jnp.broadcast_to(
        cb, (bs, nc, g, chunk, chunk))
    if g > 1:
        scores = cb.reshape(bs, nc, h, chunk, chunk) * lmat
    else:
        scores = cb * lmat if h == g else jnp.broadcast_to(
            cb, (bs, nc, h, chunk, chunk)) * lmat
    scores = scores.astype(io_dt)
    y_intra = jnp.einsum("bnhij,bnjhp->bnihp", scores, xdt,
                         preferred_element_type=F32)

    # chunk states: S_n = sum_j B_j decay(last, j) xdt_j  -> (B, nc, H, S, P)
    decay_states = jnp.exp(cum[:, :, -1:, :] - cum)          # (B, nc, Q, H)
    bgh = jnp.repeat(bb, hg, axis=3).reshape(bs, nc, chunk, h, s) if g > 1 \
        else jnp.broadcast_to(bb, (bs, nc, chunk, h, s))
    states = jnp.einsum("bnjhs,bnjh,bnjhp->bnhsp",
                        bgh.astype(F32), decay_states, xdt.astype(F32))

    # inter-chunk recurrence over nc
    chunk_decay = jnp.exp(cum[:, :, -1, :])                  # (B, nc, H)

    def step(s_run, inp):
        st, dec = inp                                        # (B,H,S,P), (B,H)
        y_state = s_run                                      # state before chunk
        s_next = s_run * dec[..., None, None] + st
        return s_next, y_state

    s0 = jnp.zeros((bs, h, s, p), F32)
    s_fin, s_prev = jax.lax.scan(step, s0,
                                 (jnp.moveaxis(states, 1, 0),
                                  jnp.moveaxis(chunk_decay, 1, 0)))
    s_prev = jnp.moveaxis(s_prev, 0, 1)                      # (B, nc, H, S, P)

    cgh = jnp.repeat(cc, hg, axis=3).reshape(bs, nc, chunk, h, s) if g > 1 \
        else jnp.broadcast_to(cc, (bs, nc, chunk, h, s))
    y_inter = jnp.einsum("bnihs,bnih,bnhsp->bnihp", cgh.astype(F32),
                         jnp.exp(cum), s_prev)
    y = (y_intra + y_inter).reshape(bs, l, h, p)
    return y, s_fin


def mamba2_block(p: Params, x: jax.Array, cfg: SSMConfig, *,
                 use_pallas: bool = False, return_state: bool = False):
    """x: (B, L, d_model) -> (B, L, d_model) [, serving state]."""
    bsz, l, d_model = x.shape
    di = cfg.expand * d_model
    nh = di // cfg.head_dim
    g, s = cfg.n_groups, cfg.state

    zxbcdt = jnp.einsum("bld,de->ble", x, p["in_proj"]).astype(x.dtype)
    z, xin, bc, dt_raw = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + 2 * g * s], axis=-1)
    conv_in = jnp.concatenate([xin, bc], -1)
    if use_pallas:
        conv_out = causal_conv1d_op(conv_in, p["conv_w"])
    else:
        conv_out = kref.causal_conv1d_ref(conv_in, p["conv_w"])
    conv_out = jax.nn.silu(conv_out.astype(F32)).astype(x.dtype)
    xc, bmat, cmat = jnp.split(conv_out, [di, di + g * s], axis=-1)

    dt = jax.nn.softplus(dt_raw.astype(F32) + p["dt_bias"])   # (B, L, H)
    a_head = -jnp.exp(p["A_log"])
    y, s_fin = ssd_chunked(xc.reshape(bsz, l, nh, cfg.head_dim), dt, a_head,
                           bmat.reshape(bsz, l, g, s),
                           cmat.reshape(bsz, l, g, s),
                           chunk=min(cfg.chunk, l))
    y = y + p["D"][None, None, :, None] * xc.reshape(bsz, l, nh, cfg.head_dim
                                                     ).astype(F32)
    y = y.reshape(bsz, l, di)
    # gated RMSNorm (Mamba2's NormGated)
    y = y * jax.nn.silu(z.astype(F32))
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + 1e-5)
    y = (y * p["norm_scale"].astype(F32)).astype(x.dtype)
    out = jnp.einsum("ble,ed->bld", y, p["out_proj"]).astype(x.dtype)
    if not return_state:
        return out
    kc = p["conv_w"].shape[0]
    pad = jnp.zeros((bsz, max(0, kc - 1 - l), conv_in.shape[-1]), conv_in.dtype)
    conv_state = jnp.concatenate([pad, conv_in[:, -(kc - 1):]], 1)
    return out, {"conv": conv_state, "ssm": s_fin}


# ---------------------------------------------------------------------------
# Decode path: O(1) state per token
# ---------------------------------------------------------------------------
def mamba2_init_state(bsz: int, d_model: int, cfg: SSMConfig, dtype
                      ) -> Dict[str, jax.Array]:
    di = cfg.expand * d_model
    nh = di // cfg.head_dim
    conv_dim = di + 2 * cfg.n_groups * cfg.state
    return {
        "conv": jnp.zeros((bsz, cfg.conv_kernel - 1, conv_dim), dtype),
        "ssm": jnp.zeros((bsz, nh, cfg.state, cfg.head_dim), F32),
    }


def mamba2_step(p: Params, x: jax.Array, state: Dict[str, jax.Array],
                cfg: SSMConfig) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (B, 1, d_model); O(1) per-token state update."""
    bsz, _, d_model = x.shape
    di = cfg.expand * d_model
    nh = di // cfg.head_dim
    g, s = cfg.n_groups, cfg.state

    zxbcdt = jnp.einsum("bld,de->ble", x, p["in_proj"],
                        preferred_element_type=F32).astype(x.dtype)
    z, xin, bc, dt_raw = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + 2 * g * s], axis=-1)
    conv_in = jnp.concatenate([xin, bc], -1)[:, 0]            # (B, conv_dim)
    window = jnp.concatenate([state["conv"], conv_in[:, None]], 1)
    conv_out = jnp.einsum("bkc,kc->bc", window.astype(F32),
                          p["conv_w"].astype(F32))
    conv_out = jax.nn.silu(conv_out).astype(x.dtype)
    xc, bvec, cvec = jnp.split(conv_out, [di, di + g * s], axis=-1)

    dt = jax.nn.softplus(dt_raw.astype(F32)[:, 0] + p["dt_bias"])  # (B, H)
    a = jnp.exp(dt * (-jnp.exp(p["A_log"]))[None, :])              # (B, H)
    xh = xc.reshape(bsz, nh, cfg.head_dim).astype(F32)
    bh = jnp.broadcast_to(bvec.reshape(bsz, g, 1, s).astype(F32),
                          (bsz, g, nh // g, s)).reshape(bsz, nh, s)
    ch = jnp.broadcast_to(cvec.reshape(bsz, g, 1, s).astype(F32),
                          (bsz, g, nh // g, s)).reshape(bsz, nh, s)
    ssm = state["ssm"] * a[..., None, None] + \
        jnp.einsum("bhs,bh,bhp->bhsp", bh, dt, xh)
    y = jnp.einsum("bhs,bhsp->bhp", ch, ssm) + p["D"][None, :, None] * xh
    y = y.reshape(bsz, di)
    y = y * jax.nn.silu(z.astype(F32)[:, 0])
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + 1e-5)
    y = (y * p["norm_scale"].astype(F32)).astype(x.dtype)
    out = jnp.einsum("be,ed->bd", y, p["out_proj"],
                     preferred_element_type=F32).astype(x.dtype)[:, None]
    return out, {"conv": window[:, 1:].astype(state["conv"].dtype), "ssm": ssm}
