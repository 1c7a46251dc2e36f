"""Differentiable MG3MConv: custom_vjp grads vs jax.grad of the oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.autodiff import conv_with_plans, make_training_plans
from repro.core.scene import ConvScene
from repro.kernels import ref
from repro.plan import ConvOp, make_plan


def _setup(b, ic, oc, hw, f, pad, std, seed=0):
    sc = ConvScene(B=b, IC=ic, OC=oc, inH=hw, inW=hw, fltH=f, fltW=f,
                   padH=pad, padW=pad, stdH=std, stdW=std)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    inp = jax.random.normal(k1, sc.in_shape(), jnp.float32)
    flt = jax.random.normal(k2, sc.flt_shape(), jnp.float32)
    cot = jax.random.normal(k3, sc.out_shape(), jnp.float32)
    return sc, inp, flt, cot


@pytest.mark.parametrize("spec", [
    (4, 8, 12, 9, 3, 1, 1),
    (2, 6, 6, 7, 1, 0, 1),
    (3, 5, 7, 8, 3, 0, 1),
    (2, 8, 4, 10, 3, 1, 2),    # strided: dIN falls back to jnp reference
])
def test_vjp_matches_oracle_grads(spec):
    sc, inp, flt, cot = _setup(*spec)

    def loss_ref(i, f):
        return jnp.sum(ref.conv_ref(i, f, sc) * cot)

    want_din, want_dflt = jax.grad(loss_ref, argnums=(0, 1))(inp, flt)
    plans = make_training_plans(sc)

    def loss_kernel(i, f):
        return jnp.sum(conv_with_plans(i, f, plans) * cot)

    got_din, got_dflt = jax.grad(loss_kernel, argnums=(0, 1))(inp, flt)
    np.testing.assert_allclose(got_din, want_din, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(got_dflt, want_dflt, rtol=2e-4, atol=2e-4)


def test_grad_input_is_itself_an_mg3m_scene():
    """The dIN computation routes through the selector like any scene."""
    sc, inp, flt, cot = _setup(4, 8, 16, 9, 3, 1, 1)
    din = make_plan(sc, ConvOp.DGRAD).execute(cot, flt)
    assert din.shape == sc.in_shape()


def test_grad_filter_shapes_and_values():
    sc, inp, flt, cot = _setup(2, 4, 5, 6, 3, 1, 1, seed=3)
    dflt = make_plan(sc, ConvOp.WGRAD).execute(inp, cot)
    assert dflt.shape == sc.flt_shape()

    def loss_ref(f):
        return jnp.sum(ref.conv_ref(inp, f, sc) * cot)

    want = jax.grad(loss_ref)(flt)
    np.testing.assert_allclose(dflt, want, rtol=2e-4, atol=2e-4)


def test_training_through_the_kernel_decreases_loss():
    """End-to-end: gradient descent through the Pallas forward kernel."""
    sc, inp, flt, _ = _setup(4, 3, 4, 8, 3, 1, 1, seed=5)
    target = ref.conv_ref(inp, jnp.ones_like(flt) * 0.1, sc)
    plans = make_training_plans(sc)

    def loss(f):
        return jnp.mean((conv_with_plans(inp, f, plans) - target) ** 2)

    f = flt
    l0 = float(loss(f))
    g = jax.jit(jax.grad(loss))
    for _ in range(80):
        f = f - 0.02 * g(f)
    assert float(loss(f)) < 0.3 * l0
