"""Plain reference for training ResNet-50 v1.5, written from the
configuration file alone (its ``stages``, ``expansion`` and ``layers``):
NHWC ``lax.conv_general_dilated``, batch norm with batch statistics,
3x3/2 max-pool, residual adds, global average pool and the biased head in
straightforward ``jax.numpy``, the mean softmax cross-entropy, its
gradients by ``jax.grad``, and AdamW as the configuration's ``optimizer``
states it.  It imports nothing of the program under test.

Parameters are a flat dict, as the program holds them: each conv's filter
``[fltH, fltW, IC, OC]`` under the layer's name, its batch norm's
``<name>.gamma`` and ``<name>.beta``, the head's ``head`` ``[C, classes]``
and ``head_b``.

Two precisions, for every convolution in all three directions:

``"highest"``  float32 products and sums (``Precision.HIGHEST``), under
               ``jax.default_matmul_precision("highest")``: the yardstick.
``"high"``     the control: three bf16 passes (hi*hi + hi*lo + lo*hi, each
               product exact in f32, f32 sums), the scheme of
               ``Precision.HIGH`` on a TPU, spelled out so that it computes
               the same on a CPU and on a TPU.  The input and filter
               gradients use the same three passes over their own operands
               (a ``custom_vjp``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "high")
HIGHEST = jax.lax.Precision.HIGHEST


def _conv(x, w, geom):
    p, s = geom
    return jax.lax.conv_general_dilated(
        x, w, (s, s), ((p, p), (p, p)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)


def split_bf16(a):
    """``a = hi + lo`` as two float32 arrays whose values are exact in bf16:
    ``hi`` is ``a``'s top 16 bits, ``lo`` the remainder rounded to bf16.
    The split is made with bits, which XLA cannot fold away."""
    a = a.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                      jnp.float32)
    return hi, (a - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _three_pass(f, a, b):
    """``f`` bilinear, at three bf16 passes: f(ah, bh) + f(ah, bl) +
    f(al, bh); products of bf16 values are exact in f32."""
    ah, al = split_bf16(a)
    bh, bl = split_bf16(b)
    return f(ah, bh) + (f(ah, bl) + f(al, bh))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _conv_high(x, w, geom):
    return _three_pass(lambda a, b: _conv(a, b, geom), x, w)


def _conv_high_fwd(x, w, geom):
    return _conv_high(x, w, geom), (x, w)


def _conv_high_bwd(geom, res, g):
    x, w = res
    dx = _three_pass(
        lambda gg, ww: jax.vjp(lambda xx: _conv(xx, ww, geom), x)[1](gg)[0],
        g, w)
    dw = _three_pass(
        lambda xx, gg: jax.vjp(lambda ww: _conv(xx, ww, geom), w)[1](gg)[0],
        x, g)
    return dx, dw


_conv_high.defvjp(_conv_high_fwd, _conv_high_bwd)


def conv(x, w, layer, precision: str):
    """One NHWC convolution of ``layer`` (a config layer entry, or any dict
    with ``pad`` and ``stride``) at ``precision``."""
    geom = (int(layer["pad"]), int(layer["stride"]))
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if precision == "highest":
        return _conv(x, w, geom)
    if precision == "high":
        return _conv_high(x, w, geom)
    raise ValueError(f"unknown precision {precision!r}; have {PRECISIONS}")


def batch_norm(z, gamma, beta, eps):
    """Training-mode batch norm over N, H and W, biased variance."""
    mean = z.mean(axis=(0, 1, 2))
    var = jnp.square(z - mean).mean(axis=(0, 1, 2))
    return (z - mean) / jnp.sqrt(var + eps) * gamma + beta


def max_pool(z):
    """3x3 max-pool, stride 2, one row and column of -inf on every side."""
    return jax.lax.reduce_window(z, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                 (1, 2, 2, 1),
                                 ((0, 0), (1, 1), (1, 1), (0, 0)))


def blocks(config):
    """``(stage, block, has_projection)`` of every bottleneck block."""
    return [(i, j, j == 0)
            for i, st in enumerate(config["stages"], start=1)
            for j in range(st["blocks"])]


def forward(config, params, images, precision: str):
    """Logits ``[B, classes]`` of NHWC ``images``."""
    layers = {l["name"]: l for l in config["layers"]}
    eps = config["bn_eps"]

    def conv_bn(x, name, relu):
        z = conv(x, params[name], layers[name], precision)
        z = batch_norm(z, params[name + ".gamma"], params[name + ".beta"],
                       eps)
        return jnp.maximum(z, 0.0) if relu else z

    x = max_pool(conv_bn(images.astype(jnp.float32), "stem", True))
    for i, j, proj in blocks(config):
        b = f"s{i}b{j}"
        y = conv_bn(conv_bn(conv_bn(x, b + ".a", True), b + ".b", True),
                    b + ".c", False)
        short = conv_bn(x, b + ".proj", False) if proj else x
        x = jnp.maximum(y + short, 0.0)
    pooled = x.mean(axis=(1, 2))
    return jnp.dot(pooled, params["head"], precision=HIGHEST) + params["head_b"]


def loss(config, params, images, labels, precision: str):
    """Mean softmax cross-entropy of integer ``labels``."""
    logits = forward(config, params, images, precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def loss_and_grads(config, params, images, labels, precision: str):
    """``(loss, grads)`` of one batch, every matmul at float32 unless the
    precision asks for the control's passes."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: loss(config, p, images, labels, precision))(params)


def adamw(config, params, grads, m, v, step):
    """The parameters after one AdamW step of the configuration's
    ``optimizer`` from moments ``m``, ``v`` after ``step`` steps: gradients
    clipped to a global norm of ``clip_norm``, linear warm-up then cosine
    decay of the learning rate, bias-corrected moments, decoupled weight
    decay on every parameter."""
    o = config["optimizer"]
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()))
    scale = jnp.minimum(1.0, o["clip_norm"] / jnp.maximum(norm, 1e-9))
    t = (step + 1).astype(jnp.float32)
    warm = o["lr"] * t / max(o["warmup_steps"], 1)
    prog = jnp.clip((t - o["warmup_steps"])
                    / max(o["total_steps"] - o["warmup_steps"], 1), 0.0, 1.0)
    cos = o["lr"] * (o["min_lr_frac"] + (1 - o["min_lr_frac"]) * 0.5
                     * (1 + jnp.cos(math.pi * prog)))
    lr = jnp.where(t < o["warmup_steps"], warm, cos)
    b1, b2 = o["beta1"], o["beta2"]
    out = {}
    for k, p in params.items():
        g = grads[k] * scale
        m1 = b1 * m[k] + (1 - b1) * g
        v1 = b2 * v[k] + (1 - b2) * jnp.square(g)
        upd = (m1 / (1 - b1 ** t)) / (jnp.sqrt(v1 / (1 - b2 ** t)) + o["eps"])
        out[k] = p - lr * (upd + o["weight_decay"] * p)
    return out
