"""Cost-model calibration: fit quality, artifact round-trip, hot-path wiring."""
import json
import math
import os
import subprocess
import sys

import pytest

from repro.core import mapping
from repro.core.mapping import (CostModel, ClassCorrection, ai_band,
                                class_key, grid_steps, select_schedule)
from repro.core.scene import ConvScene
from repro.plan.build import resolve_policy
from repro import tune

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# A machine that is a uniformly-mis-modeled roofline: 3x slower compute/BW
# than the datasheet plus a much larger per-step overhead.  The calibration
# must recover it (near-)exactly.
_TRUE_SCALE = 3.0
_TRUE_OVERHEAD_S = 40e-9


def synthetic_measure(scene, choice):
    """Deterministic ground-truth 'wall clock' consistent across candidates."""
    bm = min(choice.bm, scene.M)
    bn = min(choice.bn, scene.N)
    bk = min(choice.bk, scene.K)
    scored = mapping._score(scene, choice.schedule, bm, bn, bk)
    if scored is None:
        return math.inf
    return (max(scored.compute_s, scored.hbm_s) * _TRUE_SCALE
            + grid_steps(scene, bm, bn, bk) * _TRUE_OVERHEAD_S) * 1e6


def scene_grid():
    return [ConvScene(B=b, IC=ic, OC=oc, inH=h, inW=h, fltH=3, fltW=3,
                      padH=1, padW=1)
            for b in (2, 8, 32) for ic in (8, 64) for oc in (16, 128)
            for h in (8, 14)]


@pytest.fixture
def tuned_cache(tmp_path):
    cache = tune.ScheduleCache(str(tmp_path / "tune_cache.json"))
    for sc in scene_grid():
        tune.autotune_scene(sc, cache=cache, top_k=4,
                            measure_fn=synthetic_measure)
    cache.save()
    return cache


@pytest.fixture
def no_active_model():
    tune.set_active_cost_model(None)
    yield
    tune.set_active_cost_model(None)


# -- cost model basics ------------------------------------------------------
def test_default_model_matches_legacy_constants():
    m = mapping.DEFAULT_COST_MODEL
    assert m.mxu_rate("bfloat16") == mapping.MXU_FLOPS_BF16
    assert m.mxu_rate("float32") == mapping.MXU_FLOPS_FP32
    assert m.hbm_bw == mapping.HBM_BW
    assert not m.is_calibrated


@pytest.mark.parametrize("backend,kind,known", [
    ("cpu", "cpu", True),                 # off a TPU: the v5e target
    ("tpu", "TPU v5 lite", True),
    ("tpu", "TPU v9 imaginary", False),   # unknown chip: error, no default
])
def test_device_cost_model_keyed_by_device_kind(monkeypatch, backend, kind,
                                                known):
    import types
    monkeypatch.setattr(mapping.jax, "default_backend", lambda: backend)
    monkeypatch.setattr(mapping.jax, "devices",
                        lambda: [types.SimpleNamespace(device_kind=kind)])
    if known:
        assert mapping.device_cost_model() is mapping.DEFAULT_COST_MODEL
    else:
        with pytest.raises(ValueError, match="TPU v9 imaginary"):
            mapping.device_cost_model()


def test_score_with_default_model_is_identity():
    sc = scene_grid()[0]
    for pt in tune.enumerate_space(sc):
        a = mapping._score(sc, pt.schedule, pt.bm, pt.bn, pt.bk)
        b = mapping._score(sc, pt.schedule, pt.bm, pt.bn, pt.bk,
                           mapping.DEFAULT_COST_MODEL)
        assert a == b


def test_correction_fallback_chain():
    exact = ClassCorrection(compute_scale=0.5)
    sched = ClassCorrection(compute_scale=0.25)
    m = CostModel(corrections={class_key("TB88", "compute", "ai1"): exact,
                               class_key("TB88", "*", "*"): sched})
    assert m.correction_for("TB88", "compute", "ai1") is exact
    assert m.correction_for("TB88", "memory", "ai0") is sched
    assert m.correction_for("TB11", "compute", "ai1").compute_scale == 1.0


def test_ai_band_edges_monotone():
    bands = [ai_band(x) for x in (0.5, 10, 100, 1000)]
    assert bands == ["ai0", "ai1", "ai2", "ai3"]


def test_corrected_model_changes_prediction():
    sc = scene_grid()[0]
    base = select_schedule(sc)
    slow = CostModel(corrections={
        class_key(base.schedule, base.bound, "*"):
            ClassCorrection(compute_scale=1 / 3, bw_scale=1 / 3)})
    corrected = mapping._score(sc, base.schedule, base.bm, base.bn, base.bk,
                               slow)
    assert corrected.predicted_s > base.predicted_s


# -- sample extraction ------------------------------------------------------
def test_samples_reconstruct_measurement_scene(tuned_cache):
    samples, skipped = tune.samples_from_cache(tuned_cache)
    assert skipped == 0
    # every tuned scene contributes its winner; records whose analytic
    # favorite ran a different kernel contribute that pair too
    assert len({s.key for s in samples}) == len(scene_grid())
    assert len(samples) >= len(scene_grid())
    executions = [(s.key, s.schedule, s.bm, s.bn, s.bk) for s in samples]
    assert len(executions) == len(set(executions))  # no double-counted pair
    for s in samples:
        assert s.measured_s > 0 and math.isfinite(s.measured_s)
        assert s.scene == tune.scene_from_signature(s.key)  # no proxy used
        assert s.cls.split("|")[0] == s.schedule


def test_samples_respect_backend_filter(tuned_cache):
    be = tune.default_backend()
    assert be == "cpu+interpret", "off a TPU the tag follows the kernel mode"
    samples, _ = tune.samples_from_cache(tuned_cache, backend=be)
    assert samples
    none, skipped = tune.samples_from_cache(tuned_cache, backend="tpu")
    assert none == [] and skipped == len(tuned_cache)


def test_scene_signature_roundtrip():
    sc = ConvScene(B=3, IC=5, OC=7, inH=11, inW=13, fltH=3, fltW=5,
                   padH=1, padW=2, stdH=2, stdW=1, dtype="bfloat16")
    key = tune.scene_signature(sc, backend="cpu+interpret")
    assert tune.scene_from_signature(key) == sc


# -- fit quality (ISSUE acceptance: strict median error reduction) ----------
def test_calibration_strictly_reduces_median_error(tuned_cache):
    report = tune.fit_calibration(tuned_cache)
    assert report.n_records == len(scene_grid())
    assert report.median_err_before > 0.1          # roofline is badly off
    assert report.median_err_after < report.median_err_before
    assert report.median_err_after < 0.05          # and the fit nails it
    for f in report.classes:
        assert f.n_samples > 0
        assert f.median_err_after <= f.median_err_before + 1e-9


def test_fit_handles_thin_buckets_via_ratio():
    # Two samples in one class: below MIN_LSTSQ_SAMPLES, must ratio-fit.
    samples = []
    for sc in scene_grid()[:2]:
        choice = tune.ranked_space(sc, top_k=1)[0]
        us = synthetic_measure(sc, choice)
        samples.append(tune.calibrate.CalibSample(
            key="k", cls=class_key(choice.schedule, choice.bound, "ai0"),
            schedule=choice.schedule, compute_s=choice.compute_s,
            hbm_s=choice.hbm_s,
            n_steps=grid_steps(sc, choice.bm, choice.bn, choice.bk),
            predicted_s=choice.predicted_s, measured_s=us * 1e-6,
            scene=sc, bm=choice.bm, bn=choice.bn, bk=choice.bk))
    report = tune.fit_calibration(samples)
    assert all(f.method == "ratio" for f in report.classes)
    assert report.median_err_after <= report.median_err_before


def test_fit_skips_unusable_records(tmp_path):
    cache = tune.ScheduleCache(str(tmp_path / "c.json"))
    sc = scene_grid()[0]
    tune.autotune_scene(sc, cache=cache, top_k=2,
                        measure_fn=synthetic_measure)
    # Poison a copy of the record under another scene's key: non-finite µs.
    rec = dict(cache.get(sc))
    rec["measured_us"] = float("inf")
    poisoned = ConvScene(**{**sc.__dict__, "B": sc.B + 1})
    cache.put(poisoned, rec)
    samples, skipped = tune.samples_from_cache(cache)
    assert skipped == 1
    good_key = cache.key(sc)
    assert samples and all(s.key == good_key for s in samples)


# -- artifact persistence ---------------------------------------------------
def test_artifact_roundtrip_identical_selections(tuned_cache, tmp_path):
    report = tune.fit_calibration(tuned_cache)
    path = tune.save_calibration(report, str(tmp_path / "calib.json"))
    loaded = tune.load_calibration(path)
    fitted = report.cost_model()
    assert loaded.corrections == fitted.corrections
    assert loaded.is_calibrated and loaded.source == path
    for sc in scene_grid():
        a = select_schedule(sc, model=fitted)
        b = select_schedule(sc, model=loaded)
        assert (a.schedule, a.bm, a.bn, a.bk) == (b.schedule, b.bm, b.bn, b.bk)
        assert a.predicted_s == pytest.approx(b.predicted_s)


def test_load_rejects_wrong_version(tmp_path):
    path = str(tmp_path / "calib.json")
    with open(path, "w") as f:
        json.dump({"schema": 1, "version": "mg3m-calib-v0",
                   "corrections": {}}, f)
    with pytest.raises(ValueError, match="version"):
        tune.load_calibration(path)


def test_resolve_calibration_path_env(tmp_path, monkeypatch):
    monkeypatch.setenv(tune.calibrate.ENV_VAR, str(tmp_path / "env.json"))
    assert tune.resolve_calibration_path() == str(tmp_path / "env.json")
    assert tune.resolve_calibration_path("/x/y.json") == "/x/y.json"


# -- hot-path wiring --------------------------------------------------------
def test_active_model_used_on_selection(tuned_cache, no_active_model,
                                        monkeypatch, tmp_path):
    monkeypatch.setenv(tune.calibrate.ENV_VAR,
                       str(tmp_path / "nonexistent.json"))
    sc = scene_grid()[0]
    assert tune.active_cost_model() is mapping.DEFAULT_COST_MODEL
    assert resolve_policy(sc, None) == select_schedule(sc)

    report = tune.fit_calibration(tuned_cache)
    model = report.cost_model()
    tune.set_active_cost_model(model)
    assert tune.active_cost_model() is model
    got = resolve_policy(sc, None)
    assert got == select_schedule(sc, model=model)


def test_artifact_autoload_and_mtime_refresh(no_active_model, tuned_cache,
                                             monkeypatch, tmp_path):
    path = str(tmp_path / "calib.json")
    monkeypatch.setenv(tune.calibrate.ENV_VAR, path)
    assert tune.active_cost_model() is mapping.DEFAULT_COST_MODEL
    report = tune.fit_calibration(tuned_cache)
    tune.save_calibration(report, path)
    # force a distinct mtime so the reload check cannot alias
    os.utime(path, (1, 1))
    model = tune.active_cost_model()
    assert model.is_calibrated and model.source == path
    assert tune.active_cost_model() is model          # mtime-cached

    # corrupt artifact: warn (once) and fall back to the default model
    with open(path, "w") as f:
        f.write("{broken")
    os.utime(path, (2, 2))
    assert tune.active_cost_model() is mapping.DEFAULT_COST_MODEL


def test_malformed_artifact_never_crashes_auto_path(no_active_model,
                                                    monkeypatch, tmp_path):
    """Regression (review): a corrections entry of the wrong type raised
    TypeError through resolve_schedule's unguarded active_cost_model()."""
    path = str(tmp_path / "calib.json")
    with open(path, "w") as f:
        json.dump({"schema": 1, "version": tune.CALIB_VERSION,
                   "corrections": {"TB88|compute|ai1": None}}, f)
    monkeypatch.setenv(tune.calibrate.ENV_VAR, path)
    tune.set_default_cache(tune.ScheduleCache(str(tmp_path / "empty.json")))
    try:
        assert tune.active_cost_model() is mapping.DEFAULT_COST_MODEL
        sc = scene_grid()[0]
        assert resolve_policy(sc, "auto") == select_schedule(sc)
        assert resolve_policy(sc, None) == select_schedule(sc)
    finally:
        tune.set_default_cache(None)


def test_fit_populates_every_fallback_tier(tuned_cache):
    model = tune.fit_calibration(tuned_cache).cost_model()
    assert class_key("*", "*", "*") in model.corrections
    seen = {(s.schedule, s.cls.split("|")[1])
            for s in tune.samples_from_cache(tuned_cache)[0]}
    for sched, bound in seen:
        assert class_key(sched, bound, "*") in model.corrections
        assert class_key(sched, "*", "*") in model.corrections


def test_auto_cache_miss_uses_calibrated_model(no_active_model, tmp_path):
    """schedule="auto" with an empty cache must select under the active
    (calibrated) model, not the raw roofline."""
    tune.set_default_cache(tune.ScheduleCache(str(tmp_path / "empty.json")))
    try:
        sc = ConvScene(B=16, IC=64, OC=64, inH=14, inW=14, fltH=3, fltW=3,
                       padH=1, padW=1)
        base = select_schedule(sc)
        # Penalize the analytic favorite's class hard enough to flip the pick.
        model = CostModel(corrections={
            class_key(base.schedule, "*", "*"):
                ClassCorrection(compute_scale=1e-3, bw_scale=1e-3)})
        flipped = select_schedule(sc, model=model)
        assert flipped.schedule != base.schedule     # premise of the test
        tune.set_active_cost_model(model)
        assert resolve_policy(sc, "auto").schedule == flipped.schedule
        assert resolve_policy(sc, None).schedule == flipped.schedule
    finally:
        tune.set_default_cache(None)


# -- CLI --------------------------------------------------------------------
def test_calibrate_cli_roundtrip(tuned_cache, tmp_path):
    out = str(tmp_path / "calib.json")
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src")
               + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "calibrate.py"),
         "--cache", tuned_cache.path, "--out", out],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert "median |pred-meas|/meas" in proc.stdout
    loaded = tune.load_calibration(out)
    fitted = tune.fit_calibration(tuned_cache).cost_model()
    # The CLI fit the same records read back from disk (different sample
    # order -> last-ULP lstsq wiggle); factors must agree to float precision
    # and, the real contract, selections must be identical.
    assert set(loaded.corrections) == set(fitted.corrections)
    for cls, corr in fitted.corrections.items():
        got = loaded.corrections[cls]
        assert got.compute_scale == pytest.approx(corr.compute_scale)
        assert got.bw_scale == pytest.approx(corr.bw_scale)
        assert got.overhead_s == pytest.approx(corr.overhead_s)
    for sc in scene_grid()[:6]:
        a = select_schedule(sc, model=fitted)
        b = select_schedule(sc, model=loaded)
        assert (a.schedule, a.bm, a.bn, a.bk) == (b.schedule, b.bm, b.bn, b.bk)


def test_calibrate_cli_empty_cache_errors(tmp_path):
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src")
               + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "calibrate.py"),
         "--cache", str(tmp_path / "missing.json"),
         "--out", str(tmp_path / "calib.json")],
        capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode == 2
    assert "no tuned records" in proc.stderr
