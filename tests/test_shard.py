"""repro.shard tests: partition math (any host), and sharded-vs-single-
device parity on a forced 8-device host mesh.

The parity half runs only when the process actually has >= 8 devices —
the CI ``shard`` job forces them with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``; under the plain
tier-1 run (1 device) those tests skip.  Parity is asserted the way the
executors guarantee it: **bitwise** for batch / out-channel / halo-spatial
partitions (each output element is produced by exactly one shard running
the identical tap-and-accumulate order), and within the repo's standard
kernel tolerances (rtol=1e-4, atol=1e-4) for input-channel partitions,
whose ``psum`` reorders the K accumulation across shards.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import verify_sharded_plan
from repro.core.mapping import (SCHEDULES, SHARD_LAUNCH_OVERHEAD_S,
                                select_schedule)
from repro.core.scene import ConvScene, ceil_div, pow2_floor
from repro.models.cnn import cnn_layer_scenes
from repro.plan import ConvOp, grad_filter_scene, make_plan
from repro.plan.registry import PlanRegistry, plan_signature
from repro.shard import (PARTITION_AXES, collective_bytes, halo_geometry,
                         make_sharded_plan, make_sharded_training_plans,
                         pinned_shard_spec, select_shard_spec, shard_blocker,
                         shard_sub_scene, sharded_conv_with_plans)

RTOL, ATOL = 1e-4, 1e-4

# the acceptance set: all six paper CNNs, capped for interpret-mode CPU
SCENES = cnn_layer_scenes(batch=8, max_hw=12, max_ch=16, layers_per_net=2)

SC = ConvScene(B=16, IC=16, OC=32, inH=14, inW=14, fltH=3, fltW=3,
               padH=1, padW=1, stdH=1, stdW=1)

need8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


def _shard_count(exec_scene: ConvScene, axis: str) -> int:
    """Largest power-of-two shard count (<= 8; <= 4 for ic) this axis
    admits, or 0 when even n=2 is blocked."""
    cap = {"batch": min(8, exec_scene.N), "oc": min(8, exec_scene.M),
           "ic": min(4, exec_scene.K), "h": min(8, exec_scene.outH)}[axis]
    n = pow2_floor(max(cap, 1))
    while n >= 2 and shard_blocker(exec_scene, axis, n):
        n //= 2
    return n if n >= 2 else 0


def _rand_io(scene: ConvScene, op: ConvOp):
    shapes = {ConvOp.FPROP: (scene.in_shape(), scene.flt_shape()),
              ConvOp.DGRAD: (scene.out_shape(), scene.flt_shape()),
              ConvOp.WGRAD: (scene.in_shape(), scene.out_shape())}[op]
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    return (jax.random.normal(k1, shapes[0], jnp.float32),
            jax.random.normal(k2, shapes[1], jnp.float32))


def _pinned_plan(scene: ConvScene, op: ConvOp, axis: str, n: int):
    from repro.shard.plan import _exec_scene_for
    exec_scene, _ = _exec_scene_for(scene, op)
    choice = select_schedule(shard_sub_scene(exec_scene, axis, n))
    spec = pinned_shard_spec(scene, op, axis, n, choice)
    return make_sharded_plan(scene, op, spec=spec)


def _assert_parity(scene: ConvScene, op: ConvOp, axis: str, n: int):
    plan = _pinned_plan(scene, op, axis, n)
    assert plan.shard_tag == f"{axis}:{n}"
    assert not verify_sharded_plan(plan)
    a, b = _rand_io(scene, op)
    # the single-device plan over the same exec scene: a sharded wgrad
    # keeps the swapped scene where the unsharded one may take WBL
    policy = select_schedule(plan.exec_scene)
    want = np.asarray(make_plan(scene, op, policy=policy).execute(a, b))
    got = np.asarray(plan.execute(a, b))
    if axis == "ic":
        np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)
    else:
        np.testing.assert_array_equal(got, want)


# --------------------------------------------------------------------------
# partition math — runs on any host
# --------------------------------------------------------------------------
def test_sub_scene_dims_per_axis():
    assert shard_sub_scene(SC, "batch", 4).B == 4
    assert shard_sub_scene(SC, "oc", 8).OC == 4
    assert shard_sub_scene(SC, "ic", 4).IC == 4
    sub = shard_sub_scene(SC, "h", 4)
    assert (sub.padH, sub.apadH) == (0, 0)
    assert sub.outH == ceil_div(SC.outH, 4)


def test_sub_scene_ceil_divides_remainders():
    sc = SC.with_batch(10)   # 10 over 4 shards -> 3 per shard (ceil)
    assert shard_sub_scene(sc, "batch", 4).B == 3


def test_halo_geometry_covers_and_is_consistent():
    for sc in list(SCENES.values()) + [SC]:
        for n in (2, 3, 4, 8):
            if shard_blocker(sc, "h", n):
                continue
            geo = halo_geometry(sc, n)
            sub = shard_sub_scene(sc, "h", n)
            assert sub.inH == geo.slab
            assert sub.outH == geo.oh_sub
            assert n * geo.oh_sub >= sc.outH
            # every row any shard reads exists in the pre-padded input
            assert geo.total >= (n - 1) * geo.ch + geo.slab
            assert geo.hops >= (1 if geo.halo > 0 else 0)


def test_shard_blockers():
    assert shard_blocker(SC, "batch", 1)           # n<2 is not a partition
    assert shard_blocker(SC, "batch", SC.N + 1)    # more shards than lanes
    assert shard_blocker(SC, "oc", SC.M + 1)
    assert shard_blocker(SC, "ic", SC.K + 1)
    assert shard_blocker(SC, "h", SC.outH + 1)
    dil = dataclasses.replace(SC, dilH=2)
    assert shard_blocker(dil, "h", 2)              # lhs dilation: no h slabs
    assert shard_blocker(SC, "h", 2) is None


def test_collective_bytes_terms():
    # pure data decompositions move nothing
    assert collective_bytes(SC, "batch", 4) == 0
    assert collective_bytes(SC, "oc", 4) == 0
    geo = halo_geometry(SC, 4)
    want_h = geo.hops * geo.ch * SC.inW * SC.K * SC.N * 4
    assert collective_bytes(SC, "h", 4) == want_h
    out_bytes = SC.outH * SC.outW * SC.M * SC.N * 4
    assert collective_bytes(SC, "ic", 4) == 2 * 3 * out_bytes // 4


def test_selector_falls_back_when_collective_loses():
    """A tiny scene's per-shard win cannot pay the launch overhead — the
    joint selector must return the n=1 spec, never a predicted loss."""
    tiny = ConvScene(B=2, IC=8, OC=8, inH=4, inW=4, fltH=3, fltW=3,
                     padH=1, padW=1, stdH=1, stdW=1)
    spec = select_shard_spec(tiny, max_shards=8)
    assert not spec.is_sharded and spec.tag == "none:1"


def test_selector_total_beats_baseline_or_n1():
    """Whatever wins, its total must undercut the unsharded prediction —
    the fallback guarantee stated in the module docstring."""
    for sc in (SC, SC.with_batch(256)):
        spec = select_shard_spec(sc, max_shards=8)
        base = select_schedule(sc).predicted_s
        if spec.is_sharded:
            assert spec.predicted_s < base
            assert spec.predicted_s >= (spec.choice.predicted_s
                                        + SHARD_LAUNCH_OVERHEAD_S)
        else:
            assert spec.predicted_s == base


def test_selector_respects_axis_restriction():
    spec = select_shard_spec(SC.with_batch(256), max_shards=8,
                             axes=("batch",))
    assert spec.axis in ("batch", "none")


def test_plan_signature_shard_fragment():
    base = plan_signature(SC, ConvOp.FPROP, "analytic")
    tagged = plan_signature(SC, ConvOp.FPROP, "analytic", shard="h:8")
    assert tagged == base + "|shard=h:8"


def test_registry_sharded_and_unsharded_keys_disjoint():
    reg = PlanRegistry()
    plan = make_sharded_plan(SC, ConvOp.FPROP, max_shards=1)
    reg.put(plan)
    assert reg.get(SC, ConvOp.FPROP) is None          # unsharded key: miss
    assert reg.get(SC, ConvOp.FPROP, shard=plan.shard_tag) is plan


def test_make_sharded_plan_policy_validation():
    with pytest.raises(ValueError):
        make_sharded_plan(SC, ConvOp.FPROP, policy=select_schedule(SC))
    with pytest.raises(ValueError):
        make_sharded_plan(SC, ConvOp.FPROP, policy="forced:TB88@8/8/8")


def test_pinned_spec_device_starved():
    if jax.device_count() >= 8:
        pytest.skip("needs a device-starved host")
    choice = select_schedule(shard_sub_scene(SC, "batch", 8))
    spec = pinned_shard_spec(SC, ConvOp.FPROP, "batch", 8, choice)
    with pytest.raises(ValueError, match="device"):
        make_sharded_plan(SC, ConvOp.FPROP, spec=spec)


def test_n1_fallback_executes_and_matches():
    plan = make_sharded_plan(SC, ConvOp.FPROP, max_shards=1)
    assert not plan.spec.is_sharded
    assert not verify_sharded_plan(plan)
    a, b = _rand_io(SC, ConvOp.FPROP)
    np.testing.assert_array_equal(
        np.asarray(plan.execute(a, b)),
        np.asarray(make_plan(SC, ConvOp.FPROP).execute(a, b)))


def test_sharded_wgrad_keeps_the_swapped_route():
    """WBL is an in-process route: the sharded wgrad of an IC=3 stem keeps
    the swapped exec scene and a TB grain, and agrees with the unsharded
    plan, which takes WBL."""
    stem = ConvScene(B=8, IC=3, OC=16, inH=12, inW=12, fltH=7, fltW=7,
                     padH=3, padW=3, stdH=2, stdW=2)
    plan = make_sharded_plan(stem, ConvOp.WGRAD)
    assert plan.exec_scene == grad_filter_scene(stem)
    assert plan.inner.schedule in SCHEDULES
    assert not verify_sharded_plan(plan)
    single = make_plan(stem, ConvOp.WGRAD)
    assert single.schedule == "WBL"
    a, b = _rand_io(stem, ConvOp.WGRAD)
    np.testing.assert_allclose(np.asarray(plan.execute(a, b)),
                               np.asarray(single.execute(a, b)),
                               rtol=RTOL, atol=ATOL)


def test_make_mesh_for_clamps():
    from repro.launch.mesh import data_devices, make_host_mesh, make_mesh_for
    avail = jax.device_count()
    m = make_mesh_for(2 * avail, 2 * avail)
    assert m.devices.size <= avail
    assert make_host_mesh().shape == {"data": 1, "model": 1}
    assert len(data_devices(make_mesh_for(avail, 1))) == avail
    with pytest.raises(ValueError):
        make_mesh_for(0, 1)


# --------------------------------------------------------------------------
# parity on the forced 8-device host mesh (the acceptance criteria)
# --------------------------------------------------------------------------
@need8
@pytest.mark.parametrize("axis", PARTITION_AXES)
@pytest.mark.parametrize("name", sorted(SCENES))
def test_fprop_parity_all_paper_cnns(name, axis):
    scene = SCENES[name]
    n = _shard_count(scene, axis)
    if not n:
        pytest.skip(f"{axis} infeasible for {scene.describe()}")
    _assert_parity(scene, ConvOp.FPROP, axis, n)


@need8
@pytest.mark.parametrize("axis", PARTITION_AXES)
@pytest.mark.parametrize("name", ["alexnet/L1", "googlenet/L0",
                                  "resnet/L1", "vgg/L1"])
@pytest.mark.parametrize("op", [ConvOp.DGRAD, ConvOp.WGRAD])
def test_backward_parity(name, op, axis):
    """dgrad/wgrad through the sharded wrapper, including the strided
    forwards (googlenet/L0: 7x7 s2 -> lhs-dilated dgrad scene, rhs-dilated
    wgrad taps) whose backward exec scenes block some axes."""
    scene = SCENES[name]
    from repro.shard.plan import _exec_scene_for
    try:
        exec_scene, _ = _exec_scene_for(scene, op)
    except ValueError:
        pytest.skip("no MG3M exec scene for this direction")
    n = _shard_count(exec_scene, axis)
    if not n:
        pytest.skip(f"{axis} infeasible for {exec_scene.describe()}")
    _assert_parity(scene, op, axis, n)


@need8
def test_h_partition_remainder_shards():
    """n=3 over outH=6 strided rows: uneven chunks + multi-hop halo."""
    sc = ConvScene(B=4, IC=8, OC=8, inH=11, inW=11, fltH=3, fltW=3,
                   padH=1, padW=1, stdH=2, stdW=2)
    _assert_parity(sc, ConvOp.FPROP, "h", 3)


@need8
def test_batch_partition_remainder_shards():
    sc = SC.with_batch(10)    # 10 lanes over 4 shards: padded to 12
    _assert_parity(sc, ConvOp.FPROP, "batch", 4)


@need8
def test_joint_selection_parity_and_verify():
    """Whatever the honest selector picks for a real scene must match the
    single-device plan and pass the static verifier."""
    plans = make_sharded_training_plans(SC)
    for p in (plans.fprop, plans.dgrad, plans.wgrad):
        assert not verify_sharded_plan(p)
    a, b = _rand_io(SC, ConvOp.FPROP)
    want = np.asarray(make_plan(SC, ConvOp.FPROP).execute(a, b))
    np.testing.assert_allclose(np.asarray(plans.fprop.execute(a, b)), want,
                               rtol=RTOL, atol=ATOL)


@need8
def test_custom_vjp_grad_parity():
    from repro.core.autodiff import conv_with_plans, make_training_plans
    sc = SCENES["vgg/L1"]
    tp = make_sharded_training_plans(sc)
    ref = make_training_plans(sc)
    inp, flt = _rand_io(sc, ConvOp.FPROP)
    gs = jax.grad(lambda i, f: jnp.sum(sharded_conv_with_plans(i, f, tp) ** 2),
                  argnums=(0, 1))(inp, flt)
    gr = jax.grad(lambda i, f: jnp.sum(conv_with_plans(i, f, ref) ** 2),
                  argnums=(0, 1))(inp, flt)
    for a, b in zip(gs, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=RTOL, atol=ATOL)


@need8
def test_registry_roundtrip_sharded_plan():
    import os
    import tempfile
    reg = PlanRegistry()
    plan = _pinned_plan(SC, ConvOp.FPROP, "h", 8)
    reg.put(plan)
    path = os.path.join(tempfile.mkdtemp(), "plans.json")
    reg.save(path)
    reg2 = PlanRegistry()
    assert reg2.load(path) == 1
    re = reg2.get(SC, ConvOp.FPROP, shard="h:8")
    assert re is not None and re.spec == plan.spec
    a, b = _rand_io(SC, ConvOp.FPROP)
    np.testing.assert_array_equal(np.asarray(re.execute(a, b)),
                                  np.asarray(plan.execute(a, b)))


@need8
def test_conv_server_mesh_mode_parity_and_zero_resolution():
    """ConvServer(mesh=...) must serve bit-identical outputs to the
    single-device server with zero steady-state plan misses or builds
    (strict mode turns any miss into a hard error)."""
    from repro.launch.mesh import make_mesh_for
    from repro.serve.conv import ConvRequest, server_from_scenes
    scenes = {"a": SCENES["vgg/L1"].with_batch(1),
              "b": SCENES["resnet/L1"].with_batch(1)}
    mesh_srv = server_from_scenes(scenes, mesh=make_mesh_for(8, 1),
                                  max_batch=16, strict=True)
    ref_srv = server_from_scenes(scenes, max_batch=16, strict=True)
    mesh_srv.prewarm()
    ref_srv.prewarm()
    snap = mesh_srv.snapshot()
    reqs = []
    for i, (layer, b) in enumerate([("a", 3), ("b", 5), ("a", 16), ("b", 2)]):
        x = jax.random.normal(jax.random.PRNGKey(i),
                              scenes[layer].with_batch(b).in_shape(),
                              jnp.float32)
        reqs.append((layer, x))
    out_m = mesh_srv.serve([ConvRequest(rid=i, layer=l, x=x)
                            for i, (l, x) in enumerate(reqs)])
    out_r = ref_srv.serve([ConvRequest(rid=i, layer=l, x=x)
                           for i, (l, x) in enumerate(reqs)])
    for a, b in zip(out_m, out_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    st = mesh_srv.stats(since=snap)
    assert st["plan_misses"] == 0 and st["plan_builds"] == 0
    assert st["dispatches"] >= 1


def test_shard_counts_divide_the_ring():
    """Every candidate count divides the ring, so a plan's replicated
    shards tile the whole pool and plans of one pool share its devices."""
    from repro.shard.spec import _shard_counts
    assert _shard_counts(1) == ()
    assert _shard_counts(4) == (2, 4)
    assert _shard_counts(8) == (2, 4, 8)
    assert _shard_counts(6) == (2, 6)


@need8
def test_sub_ring_partition_runs_on_the_whole_pool():
    plan = _pinned_plan(SC, ConvOp.FPROP, "batch", 2)
    assert len(plan.devices) == jax.device_count()
    a, b = _rand_io(SC, ConvOp.FPROP)
    np.testing.assert_array_equal(
        np.asarray(plan.execute(a, b)),
        np.asarray(make_plan(SC, ConvOp.FPROP).execute(a, b)))
