"""repro.tune subsystem: search space, cache, autotune, auto dispatch, CLI."""
import json
import math
import subprocess
import sys
import time
import os

import numpy as np
import pytest

from repro.core.mapping import SCHEDULES, VMEM_BUDGET, _vmem_bytes, \
    select_schedule
from repro.core.scene import ConvScene
from repro.kernels import ref
from repro.plan.build import make_plan, resolve_policy
from repro import tune

SC = ConvScene(B=8, IC=16, OC=24, inH=10, inW=10, fltH=3, fltW=3,
               padH=1, padW=1)


@pytest.fixture
def fresh_default_cache(tmp_path):
    cache = tune.ScheduleCache(str(tmp_path / "cache.json"))
    tune.set_default_cache(cache)
    yield cache
    tune.set_default_cache(None)


# -- space ------------------------------------------------------------------
def test_space_feasible_and_covers_schedules():
    pts = tune.enumerate_space(SC)
    assert pts, "space must be non-empty"
    assert {p.schedule for p in pts} == set(SCHEDULES)
    for p in pts:
        assert _vmem_bytes(SC, p.schedule, p.bm, p.bn, p.bk) <= VMEM_BUDGET


def test_ranked_space_sorted_and_contains_analytic_winner():
    ranked = tune.ranked_space(SC)
    preds = [c.predicted_s for c in ranked]
    assert preds == sorted(preds)
    best = select_schedule(SC)
    assert ranked[0].predicted_s == pytest.approx(best.predicted_s)
    assert tune.ranked_space(SC, top_k=2) == ranked[:2]


def test_mapping_candidate_blocks_delegates_to_space():
    from repro.core.mapping import candidate_blocks
    for sched in SCHEDULES:
        assert candidate_blocks(SC, sched) == tune.block_candidates(SC, sched)


# -- cache ------------------------------------------------------------------
def test_signature_stable_across_dtype_aliases():
    a = ConvScene(**{**SC.__dict__, "dtype": "float32"})
    b = ConvScene(**{**SC.__dict__, "dtype": "<f4"})
    c = ConvScene(**{**SC.__dict__, "dtype": "f4"})
    sigs = {tune.scene_signature(s, backend="cpu+interpret") for s in (a, b, c)}
    assert len(sigs) == 1
    d = ConvScene(**{**SC.__dict__, "dtype": "bfloat16"})
    assert tune.scene_signature(d, backend="cpu+interpret") not in sigs


def test_signature_discriminates_dims_and_backend():
    other = ConvScene(**{**SC.__dict__, "B": SC.B + 1})
    assert tune.scene_signature(SC, backend="cpu+interpret") != \
        tune.scene_signature(other, backend="cpu+interpret")
    assert tune.scene_signature(SC, backend="cpu+interpret") != \
        tune.scene_signature(SC, backend="tpu")


def test_cache_roundtrip(tmp_path):
    path = str(tmp_path / "cache.json")
    cache = tune.ScheduleCache(path)
    choice = tune.ranked_space(SC)[0]
    from repro.tune.cache import choice_to_dict
    cache.put(SC, {"choice": choice_to_dict(choice), "measured_us": 42.0})
    cache.save()
    reloaded = tune.ScheduleCache(path)
    assert reloaded.get_choice(SC) == choice
    assert reloaded.hits == 1
    assert reloaded.get(ConvScene(**{**SC.__dict__, "B": 99})) is None
    assert reloaded.misses == 1


def test_cache_lru_eviction_and_merge(tmp_path):
    path = str(tmp_path / "cache.json")
    cache = tune.ScheduleCache(path, max_entries=2)
    choice = tune.ranked_space(SC)[0]
    from repro.tune.cache import choice_to_dict
    scenes = [ConvScene(**{**SC.__dict__, "B": b}) for b in (1, 2, 3)]
    for s in scenes:
        cache.put(s, {"choice": choice_to_dict(choice), "measured_us": 1.0})
    assert len(cache) == 2
    assert cache.get(scenes[0]) is None      # evicted
    # merge-on-save keeps the faster measurement on collision
    cache.save()
    slower = tune.ScheduleCache(path, max_entries=8)
    slower.put(scenes[2], {"choice": choice_to_dict(choice),
                           "measured_us": 100.0})
    slower.save()
    assert tune.ScheduleCache(path).get(scenes[2])["measured_us"] == 1.0


def test_cache_merge_prefers_exact_over_proxy(tmp_path):
    """An exact-scene measurement must beat a proxy-capped one on merge even
    when the proxy's (shrunken, incomparable) µs is smaller."""
    path = str(tmp_path / "cache.json")
    from repro.tune.cache import choice_to_dict
    choice = tune.ranked_space(SC)[0]
    proxy_run = tune.ScheduleCache(path)
    proxy_run.put(SC, {"choice": choice_to_dict(choice), "measured_us": 80.0,
                       "proxy": {"B": 2}})
    proxy_run.save()
    exact_run = tune.ScheduleCache(path)
    exact_run.put(SC, {"choice": choice_to_dict(choice),
                       "measured_us": 5000.0, "proxy": None})
    exact_run.save()
    merged = tune.ScheduleCache(path).get(SC)
    assert merged["measured_us"] == 5000.0 and merged["proxy"] is None
    # and a later proxy run cannot clobber the exact entry
    proxy_again = tune.ScheduleCache(path)
    proxy_again.put(SC, {"choice": choice_to_dict(choice), "measured_us": 1.0,
                         "proxy": {"B": 2}})
    proxy_again.save()
    assert tune.ScheduleCache(path).get(SC)["measured_us"] == 5000.0


def test_cache_lru_bound_applies_on_load(tmp_path):
    path = str(tmp_path / "cache.json")
    from repro.tune.cache import choice_to_dict
    choice = tune.ranked_space(SC)[0]
    big = tune.ScheduleCache(path, max_entries=16)
    for b in range(1, 6):
        big.put(ConvScene(**{**SC.__dict__, "B": b}),
                {"choice": choice_to_dict(choice), "measured_us": 1.0})
    big.save()
    bounded = tune.ScheduleCache(path, max_entries=2)
    assert len(bounded) == 2
    # save() from the bounded view still preserves all disk entries
    bounded.save()
    assert len(tune.ScheduleCache(path, max_entries=16)) == 5


def test_cache_tolerates_corrupt_artifact_on_init(tmp_path, capsys):
    path = str(tmp_path / "corrupt.json")
    with open(path, "w") as f:
        f.write("{truncated")
    cache = tune.ScheduleCache(path)   # must not raise: auto hot path
    assert len(cache) == 0
    assert "unreadable cache" in capsys.readouterr().err
    with pytest.raises(json.JSONDecodeError):
        cache.load()                   # explicit load stays strict


def test_cache_skips_malformed_entries_on_load(tmp_path, capsys):
    """Regression: a malformed/old-schema entry made get_choice raise
    KeyError on the schedule="auto" hot path.  Bad entries are skipped (with
    a warning) on load and dropped from merges; good entries survive."""
    path = str(tmp_path / "cache.json")
    from repro.tune.cache import choice_to_dict
    choice = tune.ranked_space(SC)[0]
    good = tune.ScheduleCache(path)
    good.put(SC, {"choice": choice_to_dict(choice), "measured_us": 7.0})
    good.save()
    with open(path) as f:
        doc = json.load(f)
    other = ConvScene(**{**SC.__dict__, "B": SC.B + 1})
    third = ConvScene(**{**SC.__dict__, "B": SC.B + 2})
    doc["entries"][good.key(other)] = {"choice": {"schedule": "TB11"},
                                       "measured_us": 1.0}   # missing blocks
    doc["entries"][good.key(third)] = "not-a-record"
    with open(path, "w") as f:
        json.dump(doc, f)

    cache = tune.ScheduleCache(path)
    assert "malformed" in capsys.readouterr().err
    assert len(cache) == 1
    assert cache.get_choice(SC) == choice           # hot path: no KeyError
    assert cache.get_choice(other) is None
    assert cache.get_choice(third) is None
    # merge-on-save also drops the junk instead of preserving it forever
    cache.save()
    with open(path) as f:
        entries = json.load(f)["entries"]
    assert set(entries) == {good.key(SC)}


def test_resolve_cache_path_env(tmp_path, monkeypatch):
    monkeypatch.setenv(tune.cache.ENV_VAR, str(tmp_path / "env.json"))
    assert tune.resolve_cache_path() == str(tmp_path / "env.json")
    assert tune.resolve_cache_path("/x/y.json") == "/x/y.json"


# -- autotune ---------------------------------------------------------------
def test_autotune_picks_measured_winner_over_analytic(tmp_path):
    """Inject timings that invert the analytic ranking: the tuner must follow
    the measurement, not the model."""
    cache = tune.ScheduleCache(str(tmp_path / "c.json"))
    analytic = select_schedule(SC)
    fake = lambda s, c: 1.0 if c.schedule != analytic.schedule else 1000.0
    t = tune.autotune_scene(SC, cache=cache, top_k=8, measure_fn=fake)
    assert t.choice.schedule != analytic.schedule
    assert not t.agrees_with_analytic
    assert t.measured_us == 1.0
    assert t.analytic_measured_us == 1000.0
    assert t.analytic_schedule == analytic.schedule
    assert t.prediction_error >= 0
    # recorded in the cache, and a second call is a pure cache hit
    hits0 = cache.hits
    t2 = tune.autotune_scene(SC, cache=cache,
                             measure_fn=lambda s, c: 1 / 0)  # must not run
    assert cache.hits == hits0 + 1
    assert t2.choice == t.choice


def test_autotune_all_candidates_failing_does_not_poison_cache(tmp_path):
    """If every candidate fails to measure, fall back to the analytic choice
    and leave the cache untouched."""
    cache = tune.ScheduleCache(str(tmp_path / "c.json"))
    t = tune.autotune_scene(SC, cache=cache, top_k=4,
                            measure_fn=lambda s, c: math.inf)
    assert t.choice == select_schedule(SC)
    assert not math.isfinite(t.measured_us)
    assert len(cache) == 0 and cache.get(SC) is None


def test_autotune_dedups_candidates_aliased_by_proxy_clipping(tmp_path):
    """On a small proxy, full-scene candidates that clip to the same executed
    kernel must be measured once, keeping the analytically-best blocks."""
    cache = tune.ScheduleCache(str(tmp_path / "c.json"))
    big = ConvScene(B=128, IC=256, OC=512, inH=14, inW=14, fltH=3, fltW=3,
                    padH=1, padW=1)
    calls = []
    t = tune.autotune_scene(big, cache=cache, top_k=16,
                            measure_batch=2, measure_max_ch=16,
                            measure_max_hw=6,
                            measure_fn=lambda s, c: calls.append(c) or 1.0)
    msc = tune.proxy_scene(big, measure_batch=2, measure_max_ch=16,
                           measure_max_hw=6)
    clipped = [(c.schedule, min(c.bm, msc.M), min(c.bn, msc.N),
                min(c.bk, msc.K)) for c in calls]
    assert len(clipped) == len(set(clipped)), "aliased kernels measured twice"
    assert t.n_candidates == len(calls) <= 16


def test_autotune_real_measurement_smoke(tmp_path):
    cache = tune.ScheduleCache(str(tmp_path / "c.json"))
    sc = ConvScene(B=4, IC=8, OC=8, inH=7, inW=7, fltH=1, fltW=1)
    t = tune.autotune_scene(sc, cache=cache, top_k=2, iters=1)
    assert math.isfinite(t.measured_us) and t.measured_us > 0
    assert t.n_candidates == 2
    assert tune.TunedChoice.from_record(cache.get(sc)) == t


def test_autotune_proxy_scene_caps_recorded(tmp_path):
    cache = tune.ScheduleCache(str(tmp_path / "c.json"))
    t = tune.autotune_scene(SC, cache=cache, top_k=1, iters=1,
                            measure_batch=2, measure_max_ch=8,
                            measure_max_hw=6)
    assert t.proxy == {"B": 2, "IC": 8, "OC": 8, "inH": 6, "inW": 6}


def test_proxy_scene_keeps_filter_window_valid():
    sc = ConvScene(B=128, IC=3, OC=64, inH=224, inW=224, fltH=11, fltW=11,
                   padH=2, padW=2, stdH=4, stdW=4)   # alexnet L0
    p = tune.proxy_scene(sc, measure_batch=2, measure_max_ch=16,
                         measure_max_hw=8)
    assert p.outH > 0 and p.outW > 0
    assert p.B == 2 and p.IC == 3 and p.OC == 16


def test_proxy_scene_min_clamp_is_stride_independent():
    """Regression: the min-spatial clamp was `fltH + stdH - 2*padH`, so a
    strided alexnet-L0 scene capped at hw=4 came back with inH=8 even though
    inH=7 (= fltH - 2*padH) already yields a valid output."""
    sc = ConvScene(B=128, IC=3, OC=64, inH=224, inW=224, fltH=11, fltW=11,
                   padH=2, padW=2, stdH=4, stdW=4)
    p = tune.proxy_scene(sc, measure_max_hw=4)
    assert p.inH == 7 and p.inW == 7   # fltH - 2*padH, not + stride
    assert p.outH > 0 and p.outW > 0


def test_proxy_scene_never_exceeds_original_dims():
    """A proxy is a stand-in for the scene — it must never be *larger*."""
    sc = ConvScene(B=2, IC=4, OC=4, inH=5, inW=5, fltH=3, fltW=3)
    p = tune.proxy_scene(sc, measure_max_hw=64)
    assert p.inH == 5 and p.inW == 5


def test_proxy_scene_property():
    pytest.importorskip("hypothesis")
    import hypothesis.strategies as st
    from hypothesis import given, settings

    @settings(max_examples=200, deadline=None)
    @given(inH=st.integers(3, 64), inW=st.integers(3, 64),
           padH=st.integers(0, 3), padW=st.integers(0, 3),
           fltH=st.integers(1, 11), fltW=st.integers(1, 11),
           stdH=st.integers(1, 4), stdW=st.integers(1, 4),
           cap=st.integers(1, 16))
    def prop(inH, inW, padH, padW, fltH, fltW, stdH, stdW, cap):
        try:
            sc = ConvScene(B=2, IC=4, OC=4, inH=inH, inW=inW, fltH=fltH,
                           fltW=fltW, padH=padH, padW=padW,
                           stdH=stdH, stdW=stdW)
        except ValueError:
            return  # invalid original scene: nothing to proxy
        p = tune.proxy_scene(sc, measure_max_hw=cap)
        assert p.outH > 0 and p.outW > 0           # proxy stays valid
        assert p.inH <= sc.inH and p.inW <= sc.inW  # never grows
        # never larger than what the cap + filter window require
        assert p.inH <= max(cap, max(fltH - 2 * padH, 1))
        assert p.inW <= max(cap, max(fltW - 2 * padW, 1))

    prop()


def test_measure_timeout_enforced_during_warmup(monkeypatch):
    """Regression: one pathological candidate could hang a batch tune far
    past timeout_s because the warmup loop never checked the budget."""
    from repro.tune import measure as measure_mod

    import repro.plan.build as build_mod
    calls = []

    class SlowPlan:
        def __init__(self, scene):
            self.scene = scene

        def execute(self, inp, flt):
            calls.append(1)
            time.sleep(0.05)
            import jax.numpy as jnp
            return jnp.zeros(self.scene.out_shape(), jnp.float32)

    monkeypatch.setattr(measure_mod, "make_operands",
                        lambda scene, seed=0: (None, None))
    monkeypatch.setattr(build_mod, "make_plan",
                        lambda scene, *a, **kw: SlowPlan(scene))
    choice = tune.ranked_space(SC, top_k=1)[0]
    t0 = time.perf_counter()
    us = tune.measure_choice(SC, choice, warmup=100, iters=3,
                             timeout_s=0.01)
    elapsed = time.perf_counter() - t0
    assert us == math.inf          # partial/expired warmup scores inf
    assert len(calls) <= 2         # stopped early, not after 100 warmups
    assert elapsed < 2.0


def test_autotune_reuses_analytic_timing_on_clipped_key(tmp_path,
                                                        monkeypatch):
    """Regression: the analytic favorite's timing was matched by full-scene
    blocks while measurements dedup on proxy-clipped keys, so an aliased
    kernel got wall-clocked twice."""
    from repro.tune import autotune as autotune_mod

    big = ConvScene(B=128, IC=256, OC=512, inH=14, inW=14, fltH=3, fltW=3,
                    padH=1, padW=1)
    caps = dict(measure_batch=2, measure_max_ch=16, measure_max_hw=6)
    msc = tune.proxy_scene(big, **caps)
    real_analytic = select_schedule(big)
    # An "analytic" favorite whose full blocks differ from every measured
    # candidate but alias one of them once clipped to the proxy scene.
    from dataclasses import replace
    fake_analytic = replace(real_analytic,
                            bm=max(real_analytic.bm, msc.M) + 8,
                            bn=max(real_analytic.bn, msc.N) + 128,
                            bk=max(real_analytic.bk, msc.K) + 8)
    monkeypatch.setattr(autotune_mod, "select_schedule",
                        lambda scene, *a, **k: fake_analytic)

    measured = []
    cache = tune.ScheduleCache(str(tmp_path / "c.json"))
    t = tune.autotune_scene(big, cache=cache, top_k=16, **caps,
                            measure_fn=lambda s, c: measured.append(c) or 1.0)
    clipped = [(c.schedule, min(c.bm, msc.M), min(c.bn, msc.N),
                min(c.bk, msc.K)) for c in measured]
    assert len(clipped) == len(set(clipped)), \
        "analytic favorite re-measured an already-clocked clipped kernel"
    assert t.analytic_measured_us == 1.0


# -- forced-schedule resolution --------------------------------------------
# VMEM-oversized for TB11: the resident filter alone (9*512*512*4 B, double-
# buffered) blows the 12 MiB budget at every candidate blocking.
BIG_TB11_INFEASIBLE = ConvScene(B=256, IC=512, OC=512, inH=8, inW=8,
                                fltH=3, fltW=3, padH=1, padW=1)


def test_forced_infeasible_schedule_raises():
    """Regression: select_schedule(allowed=("TB11",)) fell into the
    best-is-None branch and silently returned a TB88 choice."""
    with pytest.raises(ValueError, match="TB11"):
        select_schedule(BIG_TB11_INFEASIBLE, allowed=("TB11",))
    with pytest.raises(ValueError, match="TB11"):
        resolve_policy(BIG_TB11_INFEASIBLE, "TB11")
    # the unforced selector still works (TB88 escape hatch stays available)
    assert select_schedule(BIG_TB11_INFEASIBLE).schedule in SCHEDULES


def test_mg3m_conv_never_silently_substitutes_forced_schedule():
    with pytest.raises(ValueError, match="TB11"):
        make_plan(BIG_TB11_INFEASIBLE, policy="TB11")


def test_forced_feasible_schedule_still_honored():
    for sched in SCHEDULES:
        choice = resolve_policy(SC, sched)
        assert choice.schedule == sched


def test_ranked_space_restricted_schedules_never_substitute():
    with pytest.raises(ValueError, match="TB11"):
        tune.ranked_space(BIG_TB11_INFEASIBLE, schedules=("TB11",))


# -- schedule="auto" dispatch ----------------------------------------------
def test_auto_dispatch_cache_hit_and_miss(fresh_default_cache):
    cache = fresh_default_cache
    # miss: falls back to the analytic model
    assert resolve_policy(SC, "auto") == select_schedule(SC)
    assert cache.misses == 1 and cache.hits == 0
    # hit: returns the cached (deliberately non-analytic) choice exactly
    ranked = tune.ranked_space(SC)
    cached_choice = next(c for c in ranked
                         if c.schedule != select_schedule(SC).schedule)
    from repro.tune.cache import choice_to_dict
    cache.put(SC, {"choice": choice_to_dict(cached_choice),
                   "measured_us": 1.0})
    assert resolve_policy(SC, "auto") == cached_choice
    assert cache.hits == 1


def test_mg3m_conv_auto_matches_oracle(fresh_default_cache):
    """Full conv through schedule="auto" after a real tune: numerics must
    match the reference and the resolution must come from the cache."""
    import jax.numpy as jnp  # noqa: F401  (jax init)
    cache = fresh_default_cache
    tune.autotune_scene(SC, cache=cache, top_k=2, iters=1,
                        measure_max_hw=6)
    hits0 = cache.hits
    inp, flt = tune.make_operands(SC)
    got = make_plan(SC, policy="auto").execute(inp, flt)
    np.testing.assert_allclose(got, ref.conv_ref(inp, flt, SC),
                               rtol=3e-5, atol=3e-5)
    assert cache.hits == hits0 + 1


def test_mg3m_conv_accepts_explicit_choice():
    choice = tune.ranked_space(SC)[-1]   # worst-predicted, still feasible
    inp, flt = tune.make_operands(SC)
    got = make_plan(SC, policy=choice).execute(inp, flt)
    np.testing.assert_allclose(got, ref.conv_ref(inp, flt, SC),
                               rtol=3e-5, atol=3e-5)


# -- CLI end-to-end ---------------------------------------------------------
def test_tune_cli_writes_resolvable_artifact(tmp_path):
    """scripts/tune.py tunes VGG scenes on CPU-interpret and writes a cache
    artifact that the auto path then resolves from."""
    path = str(tmp_path / "cli_cache.json")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ,
               PYTHONPATH=os.path.join(repo, "src")
               + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "tune.py"),
         "--nets", "vgg", "--batch", "2", "--limit", "1", "--cache", path,
         "--top-k", "2", "--iters", "1", "--measure-max-hw", "6"],
        capture_output=True, text=True, env=env, timeout=560)
    assert proc.returncode == 0, proc.stderr
    assert "vgg_L0" in proc.stdout
    with open(path) as f:
        doc = json.load(f)
    assert doc["entries"], "artifact must contain tuned entries"

    from repro.models.cnn import cnn_scenes
    scene = cnn_scenes(2)["vgg"][0]
    cache = tune.ScheduleCache(path)
    tune.set_default_cache(cache)
    try:
        choice = resolve_policy(scene, "auto")
        assert cache.hits == 1, "auto path must resolve from the artifact"
        assert choice.schedule in SCHEDULES
    finally:
        tune.set_default_cache(None)
