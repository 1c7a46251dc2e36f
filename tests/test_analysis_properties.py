"""Property link between the static verifier and runtime behavior: a
point the verifier passes executes to reference parity, and a geometry
the verifier flags really does compute the wrong answer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st   # noqa: E402

from repro.analysis.verify import verify_point             # noqa: E402
from repro.core.mapping import ScheduleChoice              # noqa: E402
from repro.core.scene import ConvScene                     # noqa: E402
from repro.kernels import ref                              # noqa: E402
from repro.plan import make_plan                           # noqa: E402
from repro.tune.space import enumerate_space               # noqa: E402


@st.composite
def small_scenes(draw):
    f = draw(st.integers(1, 3))
    hw = draw(st.integers(4, 8))
    return ConvScene(
        B=draw(st.integers(1, 4)), IC=draw(st.integers(1, 8)),
        OC=draw(st.integers(1, 8)), inH=hw, inW=hw, fltH=f, fltW=f,
        padH=draw(st.integers(0, f - 1)), padW=draw(st.integers(0, f - 1)),
        stdH=draw(st.integers(1, 2)), stdW=draw(st.integers(1, 2)))


@given(small_scenes(), st.data())
@settings(max_examples=10, deadline=None)
def test_verified_point_matches_reference(sc, data):
    pts = enumerate_space(sc)
    assert pts, sc.describe()
    pt = data.draw(st.sampled_from(list(pts)), label="point")
    # statically clean ...
    assert verify_point(sc, pt.schedule, pt.bm, pt.bn, pt.bk) == []
    # ... and numerically right when actually executed
    k1, k2 = jax.random.split(jax.random.PRNGKey(sc.macs % 2**31))
    inp = jax.random.normal(k1, sc.in_shape(), jnp.float32)
    flt = jax.random.normal(k2, sc.flt_shape(), jnp.float32)
    choice = ScheduleChoice(pt.schedule, pt.bm, pt.bn, pt.bk,
                            0.0, 0.0, 0.0, 0)
    got = make_plan(sc, policy=choice).execute(inp, flt)
    want = ref.conv_ref(inp, flt, sc)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@given(small_scenes())
@settings(max_examples=10, deadline=None)
def test_plan_for_scene_verifies_and_matches_reference(sc):
    # the production path end to end: whatever geometry make_plan settles
    # on is statically clean and numerically right
    plan = make_plan(sc)
    from repro.analysis.verify import verify_plan
    assert verify_plan(plan) == []
    k1, k2 = jax.random.split(jax.random.PRNGKey(sc.macs % 2**31))
    inp = jax.random.normal(k1, sc.in_shape(), jnp.float32)
    flt = jax.random.normal(k2, sc.flt_shape(), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(plan.execute(inp, flt)),
        np.asarray(ref.conv_ref(inp, flt, sc)), rtol=2e-4, atol=2e-4)
