"""Plan layer: numerical parity of fprop/dgrad/wgrad plans vs the reference,
registry hit/miss/LRU/serialization behavior, and the plan-once contract —
``execute()`` performs zero schedule resolutions, zero tune-cache IO, and
zero padded-shape derivations after ``make_plan``."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.plan.build as build_mod
import repro.tune.cache as cache_mod
from repro.analysis.verify import verify_plan
from repro.core.conv import mg3m_conv_nhwc
from repro.core.mapping import ScheduleChoice
from repro.core.scene import ConvScene
from repro.kernels import ref
from repro.models.cnn import resnet_scenes
from repro.obs.metrics import default_metrics
from repro.plan import (ConvOp, PlanRegistry, default_registry, get_plan,
                        grad_filter_scene, grad_input_scene, make_plan,
                        plan_from_dict, plan_to_dict)
from repro.plan.build import policy_tag

SCENES = {
    "plain":     (4, 8, 12, 9, 3, 1, 1),
    "pointwise": (2, 6, 6, 7, 1, 0, 1),
    "remainder": (3, 5, 7, 9, 3, 0, 1),   # awkward primes
    "strided":   (2, 8, 4, 10, 3, 1, 2),  # backward -> dilated Pallas scenes
    "unpadded":  (2, 4, 6, 8, 3, 0, 1),
    "stem":      (4, 3, 8, 12, 7, 3, 2),  # wgrad on the WBL route
}

# padding > dilated-filter-extent-1: the one genuinely inexpressible adjoint
# (dgrad only; fprop and wgrad still dispatch to Pallas).
BLOCKED = (2, 4, 4, 6, 1, 1, 1)


def _scene(b, ic, oc, hw, f, pad, std):
    return ConvScene(B=b, IC=ic, OC=oc, inH=hw, inW=hw, fltH=f, fltW=f,
                     padH=pad, padW=pad, stdH=std, stdW=std)


def _operands(sc, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    inp = jax.random.normal(k1, sc.in_shape(), jnp.float32)
    flt = jax.random.normal(k2, sc.flt_shape(), jnp.float32)
    cot = jax.random.normal(k3, sc.out_shape(), jnp.float32)
    return inp, flt, cot


# -- numerical parity: all three ops through the same selector ---------------
@pytest.mark.parametrize("name", sorted(SCENES))
def test_plan_ops_match_reference(name):
    sc = _scene(*SCENES[name])
    inp, flt, cot = _operands(sc)

    def loss_ref(i, f):
        return jnp.sum(ref.conv_ref(i, f, sc) * cot)

    want_din, want_dflt = jax.grad(loss_ref, argnums=(0, 1))(inp, flt)

    got_out = make_plan(sc, ConvOp.FPROP).execute(inp, flt)
    np.testing.assert_allclose(got_out, ref.conv_ref(inp, flt, sc),
                               rtol=1e-4, atol=1e-4)
    got_din = make_plan(sc, ConvOp.DGRAD).execute(cot, flt)
    np.testing.assert_allclose(got_din, want_din, rtol=1e-4, atol=1e-4)
    got_dflt = make_plan(sc, ConvOp.WGRAD).execute(inp, cot)
    np.testing.assert_allclose(got_dflt, want_dflt, rtol=1e-4, atol=1e-4)


def test_backward_scenes_go_through_the_selector():
    """dgrad/wgrad are ConvScenes with their own (often different) grain."""
    sc = _scene(*SCENES["plain"])
    gsc = grad_input_scene(sc)
    assert (gsc.IC, gsc.OC) == (sc.OC, sc.IC)
    assert (gsc.inH, gsc.inW) == (sc.outH, sc.outW)
    wsc = grad_filter_scene(sc)
    assert (wsc.B, wsc.IC, wsc.OC) == (sc.IC, sc.B, sc.OC)
    assert (wsc.outH, wsc.outW) == (sc.fltH, sc.fltW)
    for op in (ConvOp.DGRAD, ConvOp.WGRAD):
        plan = make_plan(sc, op)
        assert not plan.uses_reference
        assert plan.choice is not None and plan.spec is not None


def test_wgrad_route_follows_the_forward_ic():
    """ResNet-50 at B=128: the stem's wgrad (IC=3) takes the WBL route
    over the forward scene and counts once; an IC=64 wgrad keeps the
    swapped scene and TB88."""
    scenes = resnet_scenes(128)
    count = lambda: default_metrics().value("repro.plan.wgrad_batch_lanes")
    before = count()
    stem = make_plan(scenes["stem"], ConvOp.WGRAD)
    assert count() == before + 1
    assert stem.schedule == "WBL" and stem.exec_scene == scenes["stem"]
    assert (stem.spec.bn, stem.spec.kp, stem.spec.bw) == (128, 8, 28)
    assert verify_plan(stem) == []
    wide = make_plan(scenes["s1b0.b"], ConvOp.WGRAD)
    assert wide.schedule == "TB88"
    assert wide.exec_scene == grad_filter_scene(scenes["s1b0.b"])
    for policy in ("tuned", "analytic"):
        assert make_plan(scenes["stem"], ConvOp.WGRAD,
                         policy=policy).schedule == "WBL"
    assert count() == before + 3


def test_forced_grains_keep_the_swapped_wgrad():
    """A forced TB88, or an exact TB88 choice, gets exactly TB88 on the
    swapped scene; a pinned WBL choice (the registry's reload) stays WBL
    and is refused where WBL cannot serve."""
    sc = _scene(*SCENES["stem"])
    forced = make_plan(sc, ConvOp.WGRAD, policy="TB88")
    assert forced.schedule == "TB88"
    assert forced.exec_scene == grad_filter_scene(sc)
    exact = ScheduleChoice("TB88", 8, 8, 128, 0.0, 0.0, 0.0, 0)
    assert make_plan(sc, ConvOp.WGRAD, policy=exact).choice == exact
    wbl = make_plan(sc, ConvOp.WGRAD)
    pinned = make_plan(sc, ConvOp.WGRAD, policy=wbl.choice)
    assert pinned == dataclasses.replace(wbl, policy=policy_tag(wbl.choice))
    assert plan_from_dict(plan_to_dict(wbl)) == wbl
    with pytest.raises(ValueError, match="does not serve the fprop"):
        make_plan(sc, ConvOp.FPROP, policy=wbl.choice)
    with pytest.raises(ValueError, match="does not serve the wgrad"):
        make_plan(_scene(*SCENES["plain"]), ConvOp.WGRAD, policy=wbl.choice)


def test_forced_policy_is_pinned_and_recorded():
    sc = _scene(*SCENES["plain"])
    plan = make_plan(sc, policy="TB88")
    assert plan.schedule == "TB88" and plan.policy == "forced:TB88"
    inp, flt, _ = _operands(sc)
    np.testing.assert_allclose(plan.execute(inp, flt),
                               ref.conv_ref(inp, flt, sc),
                               rtol=1e-4, atol=1e-4)


def test_strided_backward_dispatches_to_pallas():
    """Strided backwards are dilated MG3M scenes, not reference fallbacks."""
    sc = _scene(*SCENES["strided"])
    dplan = make_plan(sc, ConvOp.DGRAD)
    assert not dplan.uses_reference
    assert dplan.choice is not None and dplan.spec is not None
    assert dplan.exec_scene.dilH == sc.stdH, "stride became lhs dilation"
    assert dplan.spec.sentinel, "lhs-dilated scenes take the sentinel route"
    wplan = make_plan(sc, ConvOp.WGRAD)
    assert not wplan.uses_reference
    assert wplan.exec_scene.fdilH == sc.stdH, "stride-dilated wgrad taps"
    assert not make_plan(sc, ConvOp.FPROP).uses_reference


def test_blocked_dgrad_surfaces_per_op_reference_fallback():
    """Only the genuinely inexpressible op falls back — per-op metadata."""
    sc = _scene(*BLOCKED)
    dplan = make_plan(sc, ConvOp.DGRAD)
    assert dplan.uses_reference
    assert dplan.choice is None and dplan.spec is None
    assert any("padding exceeds" in n for n in dplan.notes)
    # fprop and wgrad of the same scene still dispatch to Pallas
    assert not make_plan(sc, ConvOp.FPROP).uses_reference
    assert not make_plan(sc, ConvOp.WGRAD).uses_reference


def test_forced_policy_on_blocked_op_raises_naming_the_op():
    sc = _scene(*BLOCKED)
    with pytest.raises(ValueError, match="dgrad of .* requires a reference"):
        make_plan(sc, ConvOp.DGRAD, policy="TB88")
    # the same forced policy on a *strided* forward resolves fine now
    strided = _scene(*SCENES["strided"])
    plan = make_plan(strided, ConvOp.DGRAD, policy="TB88")
    assert plan.schedule == "TB88" and not plan.uses_reference


def test_execute_validates_operand_shapes():
    sc = _scene(*SCENES["plain"])
    inp, flt, cot = _operands(sc)
    plan = make_plan(sc)
    with pytest.raises(ValueError, match="expects operands"):
        plan.execute(flt, inp)
    a_shape, b_shape, out_shape = plan.io_shapes()
    assert (a_shape, b_shape, out_shape) == (
        sc.in_shape(), sc.flt_shape(), sc.out_shape())
    assert make_plan(sc, ConvOp.DGRAD).io_shapes() == (
        sc.out_shape(), sc.flt_shape(), sc.in_shape())


# -- the plan-once contract --------------------------------------------------
def test_execute_performs_zero_resolutions_and_cache_io(monkeypatch):
    sc = _scene(*SCENES["plain"])
    inp, flt, _ = _operands(sc)
    calls = {"select": 0, "cache_get": 0, "cache_load": 0, "derive": 0}

    def counting(name, fn):
        def wrapper(*a, **kw):
            calls[name] += 1
            return fn(*a, **kw)
        return wrapper

    import repro.tune.autotune as autotune_mod
    counted_select = counting("select", build_mod.select_schedule)
    monkeypatch.setattr(build_mod, "select_schedule", counted_select)
    monkeypatch.setattr(autotune_mod, "select_schedule", counted_select)
    monkeypatch.setattr(build_mod, "derive_exec_spec",
                        counting("derive", build_mod.derive_exec_spec))
    monkeypatch.setattr(cache_mod.ScheduleCache, "get",
                        counting("cache_get", cache_mod.ScheduleCache.get))
    monkeypatch.setattr(cache_mod.ScheduleCache, "load",
                        counting("cache_load", cache_mod.ScheduleCache.load))

    # "tuned" exercises the cache path too (miss -> analytic selection).
    plan = make_plan(sc, ConvOp.FPROP, policy="tuned")
    after_build = dict(calls)
    assert after_build["select"] == 1, "plan build resolves exactly once"
    assert after_build["derive"] == 1
    assert after_build["cache_get"] == 1, "tuned policy consults the cache"

    for _ in range(5):
        plan.execute(inp, flt)
    assert calls == after_build, (
        f"execute() must not resolve/derive/touch the cache: "
        f"{after_build} -> {calls}")


# -- registry ----------------------------------------------------------------
def test_registry_hit_miss_and_identity():
    reg = PlanRegistry()
    sc = _scene(*SCENES["plain"])
    assert reg.get(sc) is None
    assert reg.stats()["misses"] == 1
    p1 = reg.get_or_build(sc)
    p2 = reg.get_or_build(sc)
    assert p1 is p2, "a registry hit returns the same frozen plan"
    assert reg.stats() == {"size": 1, "hits": 1, "misses": 2, "evictions": 0,
                           "builds": 1, "hit_rate": 1 / 3}
    # a different op / policy / dtype is a different plan
    reg.get_or_build(sc, ConvOp.DGRAD)
    reg.get_or_build(sc, policy="TB88")
    assert len(reg) == 3


def test_registry_amortizes_forced_policies():
    """put() keys on the plan's canonical policy tag — a forced-policy plan
    must be found again (policy_tag is idempotent on 'forced:*')."""
    reg = PlanRegistry()
    sc = _scene(*SCENES["plain"])
    p1 = reg.get_or_build(sc, policy="TB88")
    p2 = reg.get_or_build(sc, policy="TB88")
    assert p1 is p2 and reg.stats()["hits"] == 1
    choice = p1.choice
    q1 = reg.get_or_build(sc, policy=choice)   # pinned ScheduleChoice
    q2 = reg.get_or_build(sc, policy=choice)
    assert q1 is q2 and len(reg) == 2
    # the artifact persists the canonical key, so a warm start hits too
    import json, tempfile, os
    path = os.path.join(tempfile.mkdtemp(), "plans.json")
    reg.save(path)
    fresh = PlanRegistry()
    fresh.load(path)
    assert fresh.get(sc, policy="TB88") is not None
    with open(path) as f:
        keys = list(json.load(f)["plans"])
    assert not any("forced:forced" in k for k in keys)


def test_registry_lru_eviction():
    reg = PlanRegistry(max_plans=2)
    scenes = [_scene(2, 4, 4, 6 + i, 3, 1, 1) for i in range(3)]
    for sc in scenes:
        reg.get_or_build(sc)
    assert len(reg) == 2 and reg.stats()["evictions"] == 1
    assert reg.get(scenes[0]) is None, "LRU evicts the oldest plan"
    assert reg.get(scenes[2]) is not None
    # touching scenes[1] protects it from the next eviction
    reg.get(scenes[1])
    reg.get_or_build(scenes[0])
    assert reg.get(scenes[1]) is not None
    assert reg.get(scenes[2]) is None


def test_default_registry_amortizes_get_plan():
    sc = _scene(*SCENES["plain"])
    p1 = get_plan(sc)
    p2 = get_plan(sc)
    assert p1 is p2
    reg = default_registry()
    assert reg.hits >= 1 and len(reg) >= 1


# -- serialization -----------------------------------------------------------
def test_plan_dict_roundtrip_pins_the_choice():
    sc = _scene(*SCENES["plain"])
    plan = make_plan(sc, ConvOp.FPROP, policy="TB88")
    back = plan_from_dict(plan_to_dict(plan))
    assert back == plan


def test_registry_save_load_roundtrip(tmp_path):
    reg = PlanRegistry()
    plain = _scene(*SCENES["plain"])
    strided = _scene(*SCENES["strided"])
    blocked = _scene(*BLOCKED)
    for op in ConvOp:
        reg.get_or_build(plain, op)
        reg.get_or_build(strided, op)   # dilated-Pallas backward plans
        reg.get_or_build(blocked, op)   # includes one reference-fallback plan
    path = str(tmp_path / "plans.json")
    reg.save(path)

    fresh = PlanRegistry()
    assert fresh.load(path) == 9
    assert fresh.plans() == reg.plans()

    # warm-started plans execute without any re-resolution
    inp, flt, cot = _operands(plain)
    got = fresh.get(plain, ConvOp.FPROP).execute(inp, flt)
    np.testing.assert_allclose(got, ref.conv_ref(inp, flt, plain),
                               rtol=1e-4, atol=1e-4)
    dplan = fresh.get(strided, ConvOp.DGRAD)
    assert not dplan.uses_reference, "dilated Pallas dgrad survives pinned"
    assert dplan.exec_scene.dilH == strided.stdH
    assert fresh.get(blocked, ConvOp.DGRAD).uses_reference, \
        "reference fallback survives the roundtrip"


def test_registry_merge_on_save_keeps_concurrent_writers(tmp_path):
    """Two serving processes saving to one artifact union their plans: the
    second writer must not clobber the first's pinned plans."""
    path = str(tmp_path / "plans.json")
    a, b = PlanRegistry(), PlanRegistry()
    sa = _scene(*SCENES["plain"])
    sb = _scene(*SCENES["strided"])
    a.get_or_build(sa)
    b.get_or_build(sb, ConvOp.DGRAD)
    a.save(path)
    b.save(path)     # read-modify-write: a's plan must survive
    merged = PlanRegistry()
    assert merged.load(path) == 2
    assert merged.get(sa) is not None, "first writer's plan survived"
    assert merged.get(sb, ConvOp.DGRAD) is not None
    # collision: the in-memory plan wins over the disk copy, no duplication
    a2 = PlanRegistry()
    a2.get_or_build(sa)
    a2.save(path)
    final = PlanRegistry()
    assert final.load(path) == 2
    # malformed/stale disk entries are purged on save, not unioned back
    # forever: anything load() would skip with a warning must also drop —
    # including a pre-dilation choice-less DGRAD entry for a strided scene
    # that now resolves to Pallas (assemble_plan rejects it).
    import dataclasses, json
    with open(path) as f:
        doc = json.load(f)
    doc["plans"]["v=bogus"] = {"scene": {"B": -1}, "op": "fprop"}
    doc["plans"]["v=stale"] = {
        "scene": {f.name: getattr(sb, f.name)
                  for f in dataclasses.fields(sb)},
        "op": "dgrad", "policy": "analytic", "interpret": True,
        "use_pallas": True, "uses_reference": True, "notes": [],
        "choice": None}
    with open(path, "w") as f:
        json.dump(doc, f)
    a2.save(path)
    with open(path) as f:
        kept = json.load(f)["plans"]
    assert "v=bogus" not in kept and "v=stale" not in kept


def test_registry_load_skips_malformed_entries(tmp_path, capsys):
    reg = PlanRegistry()
    sc = _scene(*SCENES["plain"])
    reg.get_or_build(sc)
    path = str(tmp_path / "plans.json")
    reg.save(path)
    import json
    with open(path) as f:
        doc = json.load(f)
    doc["plans"]["v=bogus"] = {"scene": {"B": -1}, "op": "fprop"}
    with open(path, "w") as f:
        json.dump(doc, f)
    fresh = PlanRegistry()
    assert fresh.load(path) == 1, "malformed entry skipped, good one loaded"


# -- public-path validation (asserts replaced by ValueErrors) ----------------
def test_nhwc_channel_mismatch_raises_value_error():
    x = jnp.zeros((2, 8, 8, 6))
    w = jnp.zeros((3, 3, 5, 10))   # 5 != 6 input channels
    with pytest.raises(ValueError, match="input channels"):
        mg3m_conv_nhwc(x, w, padding=(1, 1))


def test_conv_op_shape_mismatch_raises_value_error():
    sc = _scene(*SCENES["plain"])
    inp, flt, _ = _operands(sc)
    plan = make_plan(sc)
    with pytest.raises(ValueError, match="expects operands"):
        plan.execute(inp[:-1], flt)
    with pytest.raises(ValueError, match="expects operands"):
        plan.execute(inp, flt[..., :-1])


def test_scene_rejects_unparseable_dtype():
    with pytest.raises(ValueError, match="dtype"):
        ConvScene(B=1, IC=1, OC=1, inH=4, inW=4, fltH=3, fltW=3,
                  dtype="not-a-dtype")


def test_plans_are_frozen_and_hashable():
    sc = _scene(*SCENES["plain"])
    plan = make_plan(sc)
    hash(plan)   # jit-stability requires hashable static plans
    with pytest.raises(dataclasses.FrozenInstanceError):
        plan.policy = "forced:TB88"


# -- kernel mode derived from the platform -----------------------------------
def test_kernel_mode_follows_platform(monkeypatch):
    from repro.kernels import interpret_mode
    assert interpret_mode() is True            # the CPU runs the interpreter
    assert interpret_mode(False) is False      # tests can force a compile
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_mode() is False
    assert cache_mod.default_backend() == "tpu"


def _entry_points():
    from repro.core import autodiff, conv
    from repro.kernels.causal_conv1d import causal_conv1d_op
    from repro.models.cnn import small_cnn_forward
    from repro.serve.conv import ConvServer
    from repro.serve.sched import ConvScheduler
    from repro.shard import autodiff as shard_autodiff
    from repro.shard import plan as shard_plan
    from repro.tune import autotune, measure
    return {
        "make_plan": make_plan, "assemble_plan": build_mod.assemble_plan,
        "resolve_policy": build_mod.resolve_policy,
        "registry.get": PlanRegistry.get,
        "registry.get_or_build": PlanRegistry.get_or_build,
        "registry.warm": PlanRegistry.warm, "get_plan": get_plan,
        "ConvServer": ConvServer, "ConvScheduler": ConvScheduler,
        "mg3m_conv_nhwc": conv.mg3m_conv_nhwc,
        "causal_conv1d_op": causal_conv1d_op,
        "make_training_plans": autodiff.make_training_plans,
        "make_model_plans": autodiff.make_model_plans,
        "small_cnn_forward": small_cnn_forward,
        "make_sharded_plan": shard_plan.make_sharded_plan,
        "make_sharded_training_plans":
            shard_autodiff.make_sharded_training_plans,
        "autotune_scene": autotune.autotune_scene,
        "measure_choice": measure.measure_choice,
    }


@pytest.mark.parametrize("name", sorted(_entry_points()))
def test_no_interpret_option_above_the_kernels(name):
    """Neither the kernel mode nor a Pallas-or-reference switch is an
    option of any entry point: the platform decides the one, the plan the
    other."""
    import inspect
    params = inspect.signature(_entry_points()[name]).parameters
    assert "interpret" not in params and "use_pallas" not in params


# An old artifact's (key fragment, entry fields) and whether it still loads
_OLD_ARTIFACTS = {
    "interpret": ("|int=1", {"interpret": True}, True),
    "pallas_on": ("|pl=1", {"use_pallas": True}, True),
    "pallas_off": ("|pl=0", {"use_pallas": False}, False),
}


@pytest.mark.parametrize("case", sorted(_OLD_ARTIFACTS))
def test_registry_loads_artifact_with_interpret_field(tmp_path, case):
    """An artifact written when plans carried the kernel mode (``|int=``
    in the key, ``interpret`` in the entry) or the Pallas switch (``|pl=1``,
    ``use_pallas: true``) still loads, serves its plans under today's keys,
    and is rewritten without the field.  A ``use_pallas: false`` entry
    describes a plan no longer built: ``load`` skips it, ``save`` drops
    it."""
    import json
    frag, fields, loads = _OLD_ARTIFACTS[case]
    sc = _scene(*SCENES["plain"])
    reg = PlanRegistry()
    plan = reg.get_or_build(sc)
    path = str(tmp_path / "old.json")
    reg.save(path)
    with open(path) as f:
        doc = json.load(f)
    (key, entry), = doc["plans"].items()
    old_key = key.replace("|dt=", frag + "|dt=")
    doc["plans"] = {old_key: dict(entry, **fields)}
    with open(path, "w") as f:
        json.dump(doc, f)
    fresh = PlanRegistry()
    assert fresh.load(path) == int(loads)
    got = fresh.get(sc)
    if loads:
        assert got is not None and got.choice == plan.choice
    else:
        assert got is None
    fresh.save(path)
    with open(path) as f:
        saved = json.load(f)["plans"]
    assert list(saved) == ([key] if loads else [])
    if loads:
        assert not set(fields) & set(saved[key])


# -- the conv system stands apart from the LM substrate ----------------------
_LM_SUBSTRATE = ("repro.models.transformer", "repro.models.mamba2",
                 "repro.models.moe", "repro.models.rwkv6",
                 "repro.models.layers", "repro.train.step", "repro.parallel",
                 "repro.configs")


def test_conv_system_imports_no_lm_substrate():
    """The modules the cells enter through (training, the CNN models,
    serving, plans) load no module of the LM substrate."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = ("import sys\n"
            "import repro.train.cnn, repro.models.cnn, repro.serve.sched\n"
            "import repro.plan\n"
            f"lm = {_LM_SUBSTRATE!r}\n"
            "print(sorted(m for m in sys.modules\n"
            "             if any(m == p or m.startswith(p + '.') "
            "for p in lm)))\n")
    env = dict(os.environ,
               PYTHONPATH=os.path.join(repo, "src")
               + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "[]", proc.stdout
