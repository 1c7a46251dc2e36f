"""Plan-driven CNN training: ModelPlans, the fused train step, and the
plan-once contract under training (ISSUE 9)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.autodiff import (ModelPlans, TrainingPlans, apply_conv,
                                 make_model_plans)
from repro.core.scene import ConvScene
from repro.data.pipeline import SyntheticImages
from repro.models.cnn import (cnn_forward_planned, init_cnn_from_scenes,
                              init_small_cnn, small_cnn_forward,
                              small_cnn_plans, small_cnn_reference,
                              validate_scene_chain, vgg_style_scenes)
from repro.obs.metrics import default_metrics
from repro.train import checkpoint as ckpt
from repro.train import cnn as tc
from repro.train.optimizer import AdamWConfig

B, RES, WIDTH = 8, 8, 4


def _model(width=WIDTH, batch=B):
    params = init_small_cnn(jax.random.PRNGKey(0), width=width)
    plans = small_cnn_plans(params, batch, RES)
    return params, plans


def _batches(n, batch=B, seed=3, noise=0.3):
    data = SyntheticImages(batch, RES, seed=seed, noise=noise)
    return [jax.tree.map(jnp.asarray, data.batch_at(i)) for i in range(n)]


# ---------------------------------------------------------------------------
# ModelPlans / make_model_plans
# ---------------------------------------------------------------------------
def test_model_plans_mapping_protocol():
    params, plans = _model()
    assert isinstance(plans, ModelPlans)
    assert plans.names() == ("c1", "c2", "c3")
    assert list(plans) == ["c1", "c2", "c3"]
    assert len(plans) == 3 and "c2" in plans and "zz" not in plans
    assert isinstance(plans["c1"], TrainingPlans)
    with pytest.raises(KeyError):
        plans["nope"]
    # flat (layer, op, plan) walk covers all three directions per layer
    walk = list(plans.plans())
    assert len(walk) == 9
    assert {op for _, op, _ in walk} == {"fprop", "dgrad", "wgrad"}
    assert hash(plans) == hash(plans)      # closable-over under jit
    assert "c1" in plans.describe()


def test_make_model_plans_warms_without_traffic():
    """Building a ModelPlans leaves the registry at 100% hit rate: warm
    builds everything, assembly is pure hits."""
    from repro.plan.registry import default_registry
    params, plans = _model()
    st = default_registry().stats()
    assert st["misses"] == 0
    assert st["hits"] >= 9         # 3 layers x 3 ops fetched as hits
    assert st["hit_rate"] == 1.0
    assert plans.reference_ops == {}


def test_model_plans_scene_chain_and_layouts():
    params, plans = _model()
    scenes = plans.scenes()
    validate_scene_chain(scenes)   # c1 -> c2 -> c3 chains
    assert scenes["c1"].B == B and scenes["c1"].inH == RES


def test_apply_conv_rejects_unknown_plans():
    with pytest.raises(ValueError, match="TrainingPlans"):
        apply_conv(jnp.zeros((4, 4, 3, 2)), jnp.zeros((3, 3, 3, 4)),
                   {"not": "plans"})


def test_vgg_style_scenes_chain_and_init():
    scenes = vgg_style_scenes(4, res=16, stages=((8, 1), (16, 2), (32, 2)))
    validate_scene_chain(scenes)
    params = init_cnn_from_scenes(jax.random.PRNGKey(1), scenes,
                                  n_classes=5)
    assert params["v0"].shape == (3, 3, 3, 8)
    assert params["head"].shape == (32, 5)
    plans = make_model_plans(scenes)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 16, 16, 3))
    logits = cnn_forward_planned(params, x, plans)
    assert logits.shape == (4, 5)


def test_validate_scene_chain_raises_on_break():
    s1 = ConvScene(B=2, IC=3, OC=4, inH=8, inW=8, fltH=3, fltW=3,
                   padH=1, padW=1, stdH=1, stdW=1)
    s2 = ConvScene(B=2, IC=5, OC=4, inH=8, inW=8, fltH=3, fltW=3,
                   padH=1, padW=1, stdH=1, stdW=1)
    with pytest.raises(ValueError, match="OC=4 feeds IC=5"):
        validate_scene_chain({"a": s1, "b": s2})
    with pytest.raises(ValueError, match="at least one"):
        validate_scene_chain({})


# ---------------------------------------------------------------------------
# forward refactor: plan layout end to end
# ---------------------------------------------------------------------------
def test_small_cnn_forward_plan_path_matches_reference():
    params, plans = _model()
    x = jax.random.normal(jax.random.PRNGKey(3), (B, RES, RES, 3))
    ref = small_cnn_reference(params, x)
    got = small_cnn_forward(params, x, plans=plans)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# the fused train step
# ---------------------------------------------------------------------------
def test_multi_step_loss_descent_parity_vs_reference():
    """Same seed, same data: the plan-driven step and a
    ``small_cnn_reference`` step produce allclose losses at every step, and both
    descend."""
    params, plans = _model()
    cfg = AdamWConfig(lr=1e-2, warmup_steps=1, total_steps=20)
    batches = _batches(6)

    step = tc.build_cnn_train_step(plans, cfg)
    jstep = tc.jit_train_step(step)
    state = tc.init_train_state(jax.tree.map(jnp.array, params))
    plan_losses = []
    for b in batches:
        state, ms = jstep(state, b)
        plan_losses.append(float(ms["loss"]))

    def ref_loss(p, b):
        logits = small_cnn_reference(p, b["images"])
        return tc.softmax_cross_entropy(logits, b["labels"]), {
            "accuracy": (logits.argmax(-1) == b["labels"]).mean()}

    ref_step = tc.build_cnn_train_step(plans, cfg, loss_fn=ref_loss)
    jref = tc.jit_train_step(ref_step)
    rstate = tc.init_train_state(jax.tree.map(jnp.array, params))
    ref_losses = []
    for b in batches:
        rstate, ms = jref(rstate, b)
        ref_losses.append(float(ms["loss"]))

    np.testing.assert_allclose(plan_losses, ref_losses, rtol=1e-3,
                               atol=1e-3)
    assert plan_losses[-1] < plan_losses[0]
    # the updated parameters agree too, not just the scalar trace
    for k in params:
        np.testing.assert_allclose(state.params[k], rstate.params[k],
                                   rtol=5e-3, atol=5e-3)


def test_zero_steady_state_resolutions_after_warmup():
    params, plans = _model()
    cfg = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    jstep = tc.jit_train_step(tc.build_cnn_train_step(plans, cfg))
    state = tc.init_train_state(params)
    batches = _batches(4)
    state, _ = jstep(state, batches[0])        # warmup/compile
    with tc.resolution_guard():
        for b in batches[1:]:
            state, _ = jstep(state, b)


def test_resolution_guard_raises_on_resolution():
    from repro.plan.build import make_plan
    sc = ConvScene(B=2, IC=3, OC=4, inH=6, inW=6, fltH=3, fltW=3,
                   padH=1, padW=1, stdH=1, stdW=1)
    with pytest.raises(ValueError, match="plan-once contract"):
        with tc.resolution_guard():
            make_plan(sc)                      # resolves a schedule


def test_reference_fallback_inside_training_step():
    """A 1x1 conv with padding 1 blocks dgrad only (padding > dilated
    filter extent - 1): the layer trains through the per-op jnp fallback
    while fprop/wgrad still run Pallas."""
    sc = ConvScene(B=4, IC=3, OC=6, inH=6, inW=6, fltH=1, fltW=1,
                   padH=1, padW=1, stdH=1, stdW=1)
    scenes = {"odd": sc}
    plans = make_model_plans(scenes)
    assert plans.reference_ops == {"odd": ("dgrad",)}
    params = init_cnn_from_scenes(jax.random.PRNGKey(0), scenes,
                                  n_classes=4)
    cfg = AdamWConfig(lr=1e-2, warmup_steps=1, total_steps=10)
    jstep = tc.jit_train_step(tc.build_cnn_train_step(plans, cfg))
    state = tc.init_train_state(params)
    data = SyntheticImages(4, 6, seed=5, n_classes=4, noise=0.3)
    losses = []
    for i in range(4):
        state, ms = jstep(state, jax.tree.map(jnp.asarray, data.batch_at(i)))
        losses.append(float(ms["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_microbatch_accumulation_matches_full_batch():
    """n_microbatches=2 with flat-buffer bucketing equals the full-batch
    gradient step (same global batch, mean-of-microbatch grads)."""
    params, _ = _model()
    cfg = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10,
                      clip_norm=1e9)    # clipping is nonlinear across mbs
    batch = _batches(1)[0]

    full_plans = small_cnn_plans(params, B, RES)
    jfull = tc.jit_train_step(tc.build_cnn_train_step(full_plans, cfg))
    fstate, fms = jfull(tc.init_train_state(
        jax.tree.map(jnp.array, params)), batch)

    mb_plans = small_cnn_plans(params, B // 2, RES)
    buckets = tc.make_grad_buckets(params)
    jmb = tc.jit_train_step(tc.build_cnn_train_step(
        mb_plans, cfg, n_microbatches=2, buckets=buckets))
    mstate, mms = jmb(tc.init_train_state(
        jax.tree.map(jnp.array, params)), batch)

    # losses are means over the same examples; params see the same mean grad
    np.testing.assert_allclose(float(fms["loss"]), float(mms["loss"]),
                               rtol=1e-5, atol=1e-5)
    for k in params:
        np.testing.assert_allclose(fstate.params[k], mstate.params[k],
                                   rtol=1e-4, atol=1e-5)


def test_train_step_names_microbatch_geometry_mismatch():
    params, plans = _model()                  # plans built for B
    cfg = AdamWConfig()
    step = tc.build_cnn_train_step(plans, cfg, n_microbatches=2)
    with pytest.raises(ValueError, match="microbatch"):
        step(tc.init_train_state(params), _batches(1)[0])
    with pytest.raises(ValueError, match="n_microbatches"):
        tc.build_cnn_train_step(plans, cfg, n_microbatches=0)


def test_fused_loop_matches_stepwise():
    params, plans = _model()
    cfg = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    step = tc.build_cnn_train_step(plans, cfg)
    batches = _batches(4)

    jstep = tc.jit_train_step(step)
    s1 = tc.init_train_state(jax.tree.map(jnp.array, params))
    step_losses = []
    for b in batches:
        s1, ms = jstep(s1, b)
        step_losses.append(float(ms["loss"]))

    loop = tc.build_cnn_train_loop(step, unroll=2)
    s2 = tc.init_train_state(jax.tree.map(jnp.array, params))
    stacked = {k: jnp.stack([b[k] for b in batches])
               for k in ("images", "labels")}
    s2, lms = loop(s2, stacked)
    np.testing.assert_allclose(np.asarray(lms["loss"]), step_losses,
                               rtol=1e-5, atol=1e-5)
    for k in params:
        np.testing.assert_allclose(s1.params[k], s2.params[k],
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# gradient buckets
# ---------------------------------------------------------------------------
def test_grad_buckets_roundtrip_and_packing():
    params, _ = _model()
    buckets = tc.make_grad_buckets(params, bucket_mb=0.001)
    assert buckets.n_buckets > 1               # tiny cap forces splits
    g = jax.tree.map(lambda p: jnp.full_like(p, 0.5), params)
    rt = buckets.unflatten(buckets.flatten(g))
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(rt)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    z = buckets.zeros()
    assert len(z) == buckets.n_buckets
    assert sum(int(b.size) for b in z) == sum(
        int(p.size) for p in jax.tree.leaves(params))
    with pytest.raises(ValueError, match="bucket_mb"):
        tc.make_grad_buckets(params, bucket_mb=0)


def test_grad_reduce_applies_per_bucket():
    """grad_reduce runs once per flat bucket; halving buckets halves the
    resulting update direction exactly."""
    params, plans = _model()
    cfg = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10,
                      clip_norm=1e9)
    buckets = tc.make_grad_buckets(params)
    batch = _batches(1)[0]
    j1 = tc.jit_train_step(tc.build_cnn_train_step(
        plans, cfg, buckets=buckets))
    j2 = tc.jit_train_step(tc.build_cnn_train_step(
        plans, cfg, buckets=buckets, grad_reduce=lambda b: b * 0.0))
    s1, _ = j1(tc.init_train_state(jax.tree.map(jnp.array, params)), batch)
    s2, _ = j2(tc.init_train_state(jax.tree.map(jnp.array, params)), batch)
    # zeroed grads -> only weight decay moves params; real grads move more
    d1 = sum(float(jnp.abs(a - b).sum()) for a, b in
             zip(jax.tree.leaves(s1.params), jax.tree.leaves(params)))
    d2 = sum(float(jnp.abs(a - b).sum()) for a, b in
             zip(jax.tree.leaves(s2.params), jax.tree.leaves(params)))
    assert d2 < d1


# ---------------------------------------------------------------------------
# data, metrics, checkpoint
# ---------------------------------------------------------------------------
def test_synthetic_images_deterministic_and_learnable():
    d1 = SyntheticImages(8, 8, seed=7)
    d2 = SyntheticImages(8, 8, seed=7)
    b1, b2 = d1.batch_at(3), d2.batch_at(3)
    np.testing.assert_array_equal(b1["images"], b2["images"])
    np.testing.assert_array_equal(b1["labels"], b2["labels"])
    assert b1["images"].shape == (8, 8, 8, 3)
    assert b1["images"].dtype == np.float32
    # class structure: same-class samples correlate more than cross-class
    assert not np.array_equal(d1.batch_at(0)["images"],
                              d1.batch_at(1)["images"])
    with pytest.raises(ValueError, match="divisible"):
        SyntheticImages(7, 8, n_hosts=2)


def test_train_metrics_recorded():
    m = default_metrics()
    tc.observe_step(0.01, 2.3, 8, m)
    tc.observe_step(0.02, 2.2, 8, m)
    assert m.value("repro.train.steps") == 2
    assert m.value("repro.train.examples") == 16
    assert m.value("repro.train.step_s") == 2      # histogram count
    assert m.value("repro.train.loss") == pytest.approx(2.2)
    params, plans = _model()
    rate = tc.observe_plan_hit_rate()
    assert rate == 1.0
    assert m.value("repro.train.plan_hit_rate") == 1.0


def test_profile_step_breakdown_and_drift_feed():
    from repro.obs.drift import default_monitor
    params, plans = _model()
    cfg = AdamWConfig()
    state = tc.init_train_state(params)
    batch = _batches(1)[0]
    m = default_metrics()
    out = tc.profile_step_breakdown(state, batch, plans, cfg, metrics=m)
    assert out["grads_s"] > 0 and out["update_s"] > 0
    assert m.value("repro.train.grads_s") == 1
    assert m.value("repro.train.update_s") == 1
    fed = tc.feed_drift_from_plans(plans)
    assert fed == 9                       # 3 layers x 3 non-reference ops
    assert default_monitor().stats()      # classes observed


def test_checkpoint_roundtrip_through_train_state(tmp_path):
    params, plans = _model()
    cfg = AdamWConfig(lr=1e-2, warmup_steps=1, total_steps=10)
    jstep = tc.jit_train_step(tc.build_cnn_train_step(plans, cfg))
    state = tc.init_train_state(params)
    batches = _batches(3)
    state, _ = jstep(state, batches[0])
    ckpt.save(str(tmp_path), 1, state, extra={"next_step": 1})
    like = tc.init_train_state(init_small_cnn(jax.random.PRNGKey(9),
                                              width=WIDTH))
    restored, extra = ckpt.restore(str(tmp_path), 1, like)
    assert extra["next_step"] == 1
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # training continues bit-identically from the restored state
    s1, m1 = jstep(state, batches[1])
    s2, m2 = jstep(restored, batches[1])
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=0, atol=0)


# ---------------------------------------------------------------------------
# sharded training plans (forced multi-device hosts only)
# ---------------------------------------------------------------------------
@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs a multi-device host ring")
def test_sharded_model_plans_train_step():
    params, _ = _model()
    plans = small_cnn_plans(params, B, RES, devices=tuple(jax.devices()))
    from repro.shard.autodiff import ShardedTrainingPlans
    assert isinstance(plans["c1"], ShardedTrainingPlans)
    cfg = AdamWConfig(lr=1e-2, warmup_steps=1, total_steps=10)
    jstep = tc.jit_train_step(tc.build_cnn_train_step(plans, cfg))
    state = tc.init_train_state(jax.tree.map(jnp.array, params))
    losses = []
    for b in _batches(3):
        state, ms = jstep(state, b)
        losses.append(float(ms["loss"]))
    # parity with the in-process plan step on the same data
    in_plans = small_cnn_plans(params, B, RES)
    jref = tc.jit_train_step(tc.build_cnn_train_step(in_plans, cfg))
    rstate = tc.init_train_state(jax.tree.map(jnp.array, params))
    ref_losses = []
    for b in _batches(3):
        rstate, ms = jref(rstate, b)
        ref_losses.append(float(ms["loss"]))
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-4, atol=1e-4)
