"""Per-kernel allclose sweeps: every Pallas kernel x shapes x dtypes x
schedules against the pure-jnp oracle (interpret mode)."""
import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.footprint import strip_width
from repro.core.conv import mg3m_conv_nhwc
from repro.core.mapping import ScheduleChoice
from repro.core.scene import ConvScene
from repro.kernels import mg3m_conv as K
from repro.kernels import ref
from repro.kernels.causal_conv1d import causal_conv1d_op
from repro.plan import ConvOp, build, make_plan

SCENES = [
    # (B, IC, OC, inHW, flt, pad, std)
    (8, 16, 24, 10, 3, 1, 1),
    (4, 8, 8, 7, 1, 0, 1),
    (16, 32, 48, 12, 5, 2, 2),
    (3, 5, 7, 9, 3, 0, 2),       # awkward primes
    (1, 1, 1, 4, 3, 1, 1),       # degenerate
    (2, 64, 16, 8, 3, 1, 1),     # K > M
    (128, 16, 8, 6, 2, 0, 2),    # even filter
]


def _scene(b, ic, oc, hw, f, pad, std, dtype="float32"):
    return ConvScene(B=b, IC=ic, OC=oc, inH=hw, inW=hw, fltH=f, fltW=f,
                     padH=pad, padW=pad, stdH=std, stdW=std, dtype=dtype)


@pytest.mark.parametrize("spec", SCENES)
@pytest.mark.parametrize("schedule", ["TB11", "TB18", "TB88"])
def test_mg3m_conv_schedules_match_oracle(spec, schedule):
    sc = _scene(*spec)
    k1, k2 = jax.random.split(jax.random.PRNGKey(hash(spec) % 2**31))
    inp = jax.random.normal(k1, sc.in_shape(), jnp.float32)
    flt = jax.random.normal(k2, sc.flt_shape(), jnp.float32)
    want = ref.conv_ref(inp, flt, sc)
    got = make_plan(sc, policy=schedule).execute(inp, flt)
    # fp32 accumulation order differs between the Pallas grid walk and the
    # lax oracle; spec2 (K=32*25 taps) lands ~9e-5 relative on one element.
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("spec", SCENES[:4])
def test_mg3m_conv_autoselect(spec):
    sc = _scene(*spec)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    inp = jax.random.normal(k1, sc.in_shape(), jnp.float32)
    flt = jax.random.normal(k2, sc.flt_shape(), jnp.float32)
    got = make_plan(sc).execute(inp, flt)
    np.testing.assert_allclose(got, ref.conv_ref(inp, flt, sc),
                               rtol=3e-5, atol=3e-5)


def test_mg3m_conv_bf16():
    sc = _scene(8, 16, 16, 8, 3, 1, 1, dtype="bfloat16")
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    inp = jax.random.normal(k1, sc.in_shape(), jnp.bfloat16)
    flt = jax.random.normal(k2, sc.flt_shape(), jnp.bfloat16)
    got = make_plan(sc, policy="TB88").execute(inp, flt)
    want = ref.conv_ref(inp, flt, sc)
    np.testing.assert_allclose(got.astype(np.float32),
                               want.astype(np.float32), rtol=2e-2, atol=2e-2)


def test_conv_ref_matches_direct_loop():
    """Oracle-of-the-oracle: lax conv vs the literal 7-loop (paper Fig. 1)."""
    sc = _scene(2, 3, 4, 6, 3, 1, 2)
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    inp = np.asarray(jax.random.normal(k1, sc.in_shape(), jnp.float32))
    flt = np.asarray(jax.random.normal(k2, sc.flt_shape(), jnp.float32))
    want = ref.conv_direct_ref(inp, flt, sc)
    got = ref.conv_ref(jnp.asarray(inp), jnp.asarray(flt), sc)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_nhwc_wrapper_roundtrip():
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 9, 9, 6))
    w = jax.random.normal(jax.random.PRNGKey(4), (3, 3, 6, 10))
    got = mg3m_conv_nhwc(x, w, stride=(2, 2), padding=(1, 1))
    dn = jax.lax.conv_dimension_numbers(x.shape, w.shape,
                                        ("NHWC", "HWIO", "NHWC"))
    want = jax.lax.conv_general_dilated(x, w, (2, 2), ((1, 1), (1, 1)),
                                        dimension_numbers=dn)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("shape", [(2, 32, 16, 4), (1, 7, 5, 3),
                                   (3, 100, 64, 4), (2, 16, 16, 2),
                                   (1, 64, 128, 4)])
def test_causal_conv1d_matches_oracle(shape):
    b, l, d, k = shape
    k1, k2 = jax.random.split(jax.random.PRNGKey(l * d))
    x = jax.random.normal(k1, (b, l, d), jnp.float32)
    w = jax.random.normal(k2, (k, d), jnp.float32)
    got = causal_conv1d_op(x, w, block_l=16, block_d=8)
    want = ref.causal_conv1d_ref(x, w)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_causal_conv1d_is_causal():
    """Changing a future input must not change past outputs."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    x = jax.random.normal(k1, (1, 32, 8), jnp.float32)
    w = jax.random.normal(k2, (4, 8), jnp.float32)
    y1 = causal_conv1d_op(x, w, block_l=8, block_d=8)
    x2 = x.at[:, 20].add(100.0)
    y2 = causal_conv1d_op(x2, w, block_l=8, block_d=8)
    np.testing.assert_allclose(y1[:, :20], y2[:, :20], rtol=1e-6, atol=1e-6)
    assert not np.allclose(y1[:, 20:], y2[:, 20:])


def _dot_precisions(jaxpr):
    """``precision`` of every dot_general in a jaxpr, nested ones included
    (the Pallas kernel body is a jaxpr parameter of ``pallas_call``)."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(eqn.params["precision"])
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                found += _dot_precisions(inner)
    return found


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("schedule", ["TB11", "TB18", "TB88"])
def test_kernel_dot_precision_follows_dtype(schedule, dtype):
    """An f32 scene contracts at f32 precision on the MXU whatever the
    enclosing default (Mosaic's own default rounds f32 products to bf16);
    a bf16 scene keeps the default."""
    sc = _scene(*SCENES[0], dtype=dtype)
    x = jnp.zeros(sc.in_shape(), dtype)
    w = jnp.zeros(sc.flt_shape(), dtype)
    with jax.default_matmul_precision("bfloat16"):
        jaxpr = jax.make_jaxpr(
            make_plan(sc, policy=schedule).execute)(x, w)
    precs = _dot_precisions(jaxpr.jaxpr)
    assert precs
    highest = (jax.lax.Precision.HIGHEST,) * 2
    assert all((p == highest) == (dtype == "float32") for p in precs), precs


# --------------------------------------------------------------------------
# strips of output columns per TB11/TB18 grid step
# --------------------------------------------------------------------------
# (schedule, B, IC, OC, inHW, flt, pad, std, fdil, strip widths to launch)
STRIP_CASES = {
    "tb11_w14": ("TB11", 8, 8, 16, 14, 3, 1, 1, 1, (14, 7, 2)),
    "tb11_w16_n1": ("TB11", 1, 8, 16, 16, 3, 1, 1, 1, (16, 4)),
    "tb11_prime_w7": ("TB11", 8, 8, 16, 7, 3, 1, 1, 1, (7,)),
    "tb11_stride2": ("TB11", 8, 8, 16, 15, 3, 1, 2, 1, (8, 2)),
    "tb11_fdil2": ("TB11", 8, 8, 16, 12, 3, 2, 1, 2, (12, 3)),
    "tb11_k3": ("TB11", 8, 3, 16, 14, 3, 1, 1, 1, (14,)),
    "tb18_w14": ("TB18", 8, 8, 16, 14, 3, 1, 1, 1, (14, 7)),
    "tb18_stride2": ("TB18", 8, 8, 16, 15, 3, 1, 2, 1, (8, 4)),
    "tb18_fdil2_k3": ("TB18", 8, 3, 16, 12, 3, 2, 1, 2, (12,)),
    "tb18_n128": ("TB18", 128, 8, 16, 6, 3, 1, 1, 1, (6, 3)),
}


def _run_strip(sc, schedule, bw, inp, flt):
    """The plan's launch of ``sc`` under ``schedule`` at strip width
    ``bw`` (TB18 slices OC in halves so the slice loop runs too)."""
    policy = (ScheduleChoice("TB18", sc.M // 2, sc.N, sc.K, 0.0, 0.0, 0.0, 0)
              if schedule == "TB18" else schedule)
    spec = dataclasses.replace(make_plan(sc, policy=policy).spec, bw=bw)
    return np.asarray(build._exec_fprop(inp, flt, sc, spec))


@pytest.mark.parametrize("case", sorted(STRIP_CASES))
def test_strip_matches_oracle_and_one_pixel_steps(case):
    """A strip computes each pixel with the same dots in the same order as
    a one-pixel step: bit-identical to ``bw=1``, and the oracle's answer."""
    schedule, b, ic, oc, hw, f, pad, std, fdil, widths = STRIP_CASES[case]
    sc = ConvScene(B=b, IC=ic, OC=oc, inH=hw, inW=hw, fltH=f, fltW=f,
                   padH=pad, padW=pad, stdH=std, stdW=std, fdilH=fdil,
                   fdilW=fdil)
    assert sc.outW == widths[0]
    k1, k2 = jax.random.split(jax.random.PRNGKey(sum(map(ord, case))))
    inp = jax.random.normal(k1, sc.in_shape(), jnp.float32)
    flt = jax.random.normal(k2, sc.flt_shape(), jnp.float32)
    one = _run_strip(sc, schedule, 1, inp, flt)
    np.testing.assert_allclose(one, ref.conv_ref(inp, flt, sc),
                               rtol=1e-5, atol=1e-5)
    for bw in widths:
        np.testing.assert_array_equal(_run_strip(sc, schedule, bw, inp, flt),
                                      one)


def test_sentinel_route_keeps_one_pixel_steps():
    """An lhs-dilated scene (the dgrad of a strided conv) reads a compact
    input through the zero sentinel: its taps are not contiguous columns,
    so it launches ``bw=1`` and refuses a strip."""
    fwd = _scene(4, 8, 16, 9, 3, 1, 2)
    plan = make_plan(fwd, ConvOp.DGRAD)
    sc = plan.exec_scene
    assert sc.dilW == 2 and plan.spec.sentinel and plan.spec.bw == 1
    assert strip_width(sc, plan.schedule, plan.spec.bm, sc.N, sc.K) == 1
    in_shape = (sc.inH + 1, sc.inW + 1, sc.K, sc.N)
    with pytest.raises(ValueError, match="sentinel"):
        K.kernel_grid_spec(sc, "TB11", in_shape=in_shape,
                           flt_shape=sc.flt_shape(), bw=sc.outW)
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    d_out = jax.random.normal(k1, fwd.out_shape(), jnp.float32)
    flt = jax.random.normal(k2, fwd.flt_shape(), jnp.float32)
    zero = jnp.zeros(fwd.in_shape(), jnp.float32)
    _, vjp = jax.vjp(lambda i: ref.conv_ref(i, flt, fwd), zero)
    np.testing.assert_allclose(plan.execute(d_out, flt), vjp(d_out)[0],
                               rtol=1e-5, atol=1e-5)


# (schedule, bm, bn, bk, strip) of each benchmark layer: the selector's
# choices are the per-pixel ones, the strip is the footprint's.
_BENCH_CHOICES = {
    ("vgg16-fig13", 128): (
        ("TB11", 64, 128, 3, 32), ("TB11", 64, 128, 64, 32),
        ("TB11", 128, 128, 64, 28), ("TB11", 128, 128, 128, 28),
        ("TB11", 256, 128, 128, 14), ("TB11", 256, 128, 256, 8),
        ("TB11", 512, 128, 256, 2), ("TB18", 256, 128, 512, 2),
        ("TB18", 256, 128, 512, 2)),
    **{("allcnn-c", b): (
        ("TB11", 96, b, 3, 32), ("TB11", 96, b, 96, 32),
        ("TB11", 96, b, 96, 16), ("TB11", 192, b, 96, 16),
        ("TB11", 192, b, 192, 16), ("TB11", 192, b, 192, 8),
        ("TB11", 192, b, 192, 6), ("TB11", 192, b, 192, 6),
        ("TB11", 10, b, 192, 6)) for b in (1, 8)},
}


def _bench_layer(config, batch, index):
    path = (pathlib.Path(__file__).resolve().parents[1] / "bench"
            / "configs" / f"{config}.json")
    layer = json.loads(path.read_text())["layers"][index]
    return ConvScene(B=batch, IC=layer["IC"], OC=layer["OC"],
                     inH=layer["in_hw"], inW=layer["in_hw"],
                     fltH=layer["flt"], fltW=layer["flt"],
                     padH=layer["pad"], padW=layer["pad"],
                     stdH=layer["stride"], stdW=layer["stride"])


@pytest.mark.parametrize("config,batch,index", [
    (c, b, i) for (c, b), rows in sorted(_BENCH_CHOICES.items())
    for i in range(len(rows))])
def test_bench_layer_choice_and_strip(config, batch, index):
    """The strip leaves schedule selection alone: every benchmark layer
    keeps its (schedule, bm, bn, bk), and launches the strip its output
    width and VMEM allow."""
    plan = make_plan(_bench_layer(config, batch, index))
    c = plan.choice
    assert ((c.schedule, c.bm, c.bn, c.bk, plan.spec.bw)
            == _BENCH_CHOICES[(config, batch)][index])


# (B, IC, OC, inHW, flt, pad, std, dtype) of forward scenes whose wgrad
# takes the batch-on-lanes route (WBL); the remainder column is the
# stride remainder the swapped scene would grow its output by.
WBL_CASES = {
    "ic3_7x7_s2_p3_rem": ((8, 3, 8, 16, 7, 3, 2, "float32"), 1),
    "ic1_7x7_s2_p3": ((8, 1, 4, 11, 7, 3, 2, "float32"), 0),
    "ic1_3x3_s1_p0": ((8, 1, 8, 9, 3, 0, 1, "float32"), 0),
    "ic3_3x3_s2_p0_odd": ((8, 3, 16, 10, 3, 0, 2, "float32"), 1),
    "ic3_7x7_s1_p3": ((8, 3, 8, 9, 7, 3, 1, "float32"), 0),
    "ic3_3x3_b256": ((256, 3, 8, 7, 3, 1, 2, "float32"), 0),
    "ic3_3x3_bf16": ((8, 3, 8, 9, 3, 1, 1, "bfloat16"), 0),
}


@pytest.mark.parametrize("case", sorted(WBL_CASES))
def test_wgrad_batch_lanes_matches_reference(case):
    """The WBL weight gradient equals the reference's, and is bit-identical
    at every strip width: each dOUT pixel adds its taps in the same order
    whatever the strip."""
    args, rem = WBL_CASES[case]
    sc = _scene(*args)
    assert (sc.inW + 2 * sc.padW - sc.fltW) % sc.stdW == rem
    plan = make_plan(sc, ConvOp.WGRAD)
    assert plan.schedule == "WBL" and plan.exec_scene == sc
    assert plan.spec.np_ % 128 == 0 and plan.spec.kp % 8 == 0
    k1, k2 = jax.random.split(jax.random.PRNGKey(sum(map(ord, case))))
    inp = jax.random.normal(k1, sc.in_shape(), jnp.float32).astype(sc.dtype)
    cot = jax.random.normal(k2, sc.out_shape(), jnp.float32).astype(sc.dtype)
    got = np.asarray(plan.execute(inp, cot), np.float32)
    assert got.shape == sc.flt_shape()
    want = np.asarray(build._ref_wgrad(inp, cot, sc), np.float32)
    tol = 2e-2 if sc.dtype == "bfloat16" else 1e-4
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * 2)
    for bw in (1, sc.outW):
        spec = dataclasses.replace(plan.spec, bw=bw)
        np.testing.assert_array_equal(
            np.asarray(build._exec_wgrad(inp, cot, sc, spec), np.float32),
            got)
