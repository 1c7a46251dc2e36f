"""Plain reference for a stack of 2-D convolutions, written from the
configuration file alone: ``lax.conv_general_dilated`` in the engine's plan
layout (IN ``[H, W, IC, B]``, FLT ``[fltH, fltW, IC, OC]``, OUT
``[H, W, OC, B]``), with the configuration's activation between layers when
it is ``chained``.  It imports nothing of the program under test.

Two precisions:

``"highest"``  float32 products and sums (``Precision.HIGHEST``): what an
               f32 configuration states.  This is the yardstick.
``"high"``     the control: three bf16 passes (hi*hi + hi*lo + lo*hi, each
               product exact in f32, f32 sums), the scheme of
               ``Precision.HIGH`` on a TPU, spelled out so that it computes
               the same on a CPU and on a TPU.
               It is the step below f32 that a later change could be
               tempted to take, and the comparison must fail it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "high")


def _conv(x, w, layer, precision_arg=None, preferred=None):
    p, s = layer["pad"], layer["stride"]
    return jax.lax.conv_general_dilated(
        x, w, (s, s), ((p, p), (p, p)),
        dimension_numbers=("HWCN", "HWIO", "HWCN"),
        precision=precision_arg, preferred_element_type=preferred)


def _split_bf16(a):
    """``a = hi + lo`` with ``hi`` exact in bf16 (``a``'s top 16 bits) and
    ``lo`` the remainder rounded to bf16.  The split is made with bits, not
    with a round trip through bf16, which XLA may fold away on a TPU."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                      jnp.float32)
    return hi.astype(jnp.bfloat16), (a - hi).astype(jnp.bfloat16)


def conv(x, w, layer, precision: str):
    """One convolution of ``layer`` (a config layer entry) at ``precision``."""
    if precision == "highest":
        return _conv(x.astype(jnp.float32), w.astype(jnp.float32), layer,
                     jax.lax.Precision.HIGHEST)
    if precision == "high":
        xh, xl = _split_bf16(x.astype(jnp.float32))
        wh, wl = _split_bf16(w.astype(jnp.float32))
        c = lambda a, b: _conv(a, b, layer, preferred=jnp.float32)
        return c(xh, wh) + (c(xh, wl) + c(xl, wh))
    raise ValueError(f"unknown precision {precision!r}; have {PRECISIONS}")


def activation(config):
    name = config.get("activation")
    if name is None:
        return lambda z: z
    if name == "relu":
        return lambda z: jnp.maximum(z, 0.0)
    raise ValueError(f"unknown activation {name!r}")


def layer_output(config, i: int, x, w, precision: str):
    """Output of layer ``i`` alone on its own input (unchained configs)."""
    return activation(config)(conv(x, w, config["layers"][i], precision))


def chain_output(config, x, weights, precision: str):
    """Output of the whole chain (chained configs), activation after every
    layer."""
    act = activation(config)
    for layer, w in zip(config["layers"], weights):
        x = act(conv(x, w, layer, precision))
    return x
