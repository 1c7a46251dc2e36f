"""Pure-jnp oracles for every kernel in this package.

All reference functions use the paper's data layouts:
  IN  [inH, inW, IC, B]
  FLT [fltH, fltW, IC, OC]
  OUT [outH, outW, OC, B]
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.scene import ConvScene


def conv_ref(inp: jax.Array, flt: jax.Array, scene: ConvScene) -> jax.Array:
    """Oracle via lax.conv_general_dilated in the paper's layouts, at
    ``precision=HIGHEST`` so that on a TPU it is an f32 reference.

    Covers the full dilated scene family: ``dilH/dilW`` map to
    ``lhs_dilation`` (transposed-conv / dgrad scenes), ``fdilH/fdilW`` to
    ``rhs_dilation`` (atrous / wgrad scenes), and ``apadH/apadW`` to the
    asymmetric high-side padding a stride-remainder adjoint needs."""
    dn = jax.lax.conv_dimension_numbers(
        inp.shape, flt.shape, ("HWCN", "HWIO", "HWCN"))
    out = jax.lax.conv_general_dilated(
        inp.astype(jnp.float32),
        flt.astype(jnp.float32),
        window_strides=(scene.stdH, scene.stdW),
        padding=((scene.padH, scene.padH + scene.apadH),
                 (scene.padW, scene.padW + scene.apadW)),
        lhs_dilation=(scene.dilH, scene.dilW),
        rhs_dilation=(scene.fdilH, scene.fdilW),
        dimension_numbers=dn,
        precision=jax.lax.Precision.HIGHEST,
    )
    return out.astype(inp.dtype)


def conv_direct_ref(inp: np.ndarray, flt: np.ndarray, scene: ConvScene) -> np.ndarray:
    """Literal 7-loop direct convolution (paper Fig. 1), numpy, tiny shapes only.

    Exists to validate conv_ref itself (oracle-of-the-oracle).  Dilation
    semantics spelled out: tap (fh, fw) of output pixel (oh, ow) lands on
    *dilated* input coordinate ``oh*std + fh*fdil - pad``, which is a stored
    element iff it is a non-negative multiple of ``dil`` inside the input."""
    out = np.zeros(scene.out_shape(), dtype=np.float64)
    inp = np.asarray(inp, dtype=np.float64)
    flt = np.asarray(flt, dtype=np.float64)
    for b in range(scene.B):
        for oc in range(scene.OC):
            for oh in range(scene.outH):
                for ow in range(scene.outW):
                    acc = 0.0
                    for ic in range(scene.IC):
                        for fh in range(scene.fltH):
                            for fw in range(scene.fltW):
                                qh = oh * scene.stdH + fh * scene.fdilH - scene.padH
                                qw = ow * scene.stdW + fw * scene.fdilW - scene.padW
                                if qh % scene.dilH or qw % scene.dilW:
                                    continue   # dilation hole
                                ih, iw = qh // scene.dilH, qw // scene.dilW
                                if 0 <= ih < scene.inH and 0 <= iw < scene.inW:
                                    acc += inp[ih, iw, ic, b] * flt[fh, fw, ic, oc]
                    out[oh, ow, oc, b] = acc
    return out.astype(np.asarray(inp).dtype)


def mm_unit_ref(flt_mtx: jax.Array, in_mtx: jax.Array) -> jax.Array:
    """The paper's MM_unit: OUT[OC,B] = FLT[IC,OC]^T @ IN[IC,B] (Eq. 2)."""
    return jax.lax.dot_general(
        flt_mtx, in_mtx,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(in_mtx.dtype)


def causal_conv1d_ref(x: jax.Array, w: jax.Array) -> jax.Array:
    """Depthwise causal conv1d (Mamba2 conv), x: [B, L, D], w: [K, D].

    y[b, l, d] = sum_k w[k, d] * x[b, l - (K-1) + k, d], zeros off the left edge.
    """
    k = w.shape[0]
    xf = x.astype(jnp.float32)
    pad = jnp.pad(xf, ((0, 0), (k - 1, 0), (0, 0)))
    y = jnp.zeros_like(xf)
    for i in range(k):
        y = y + w[i].astype(jnp.float32)[None, None, :] * \
            jax.lax.dynamic_slice_in_dim(pad, i, x.shape[1], axis=1)
    return y.astype(x.dtype)
