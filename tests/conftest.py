"""Test isolation: a developer's real tune cache / calibration artifact in
``~/.cache/repro`` must never leak into assertions about analytic selection
(and test runs must never pollute those artifacts)."""
import pytest


@pytest.fixture(autouse=True)
def _isolated_tune_artifacts(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE",
                       str(tmp_path / "isolated_tune_cache.json"))
    monkeypatch.setenv("REPRO_CALIBRATION",
                       str(tmp_path / "isolated_calibration.json"))
    from repro import plan, tune
    from repro.obs import (set_default_metrics, set_default_monitor,
                           set_default_tracer)
    tune.set_default_cache(None)
    tune.set_active_cost_model(None)
    plan.set_default_registry(None)
    # fresh process-global obs state per test: counters from one test (or an
    # enabled default tracer) must never leak into another's assertions
    set_default_metrics(None)
    set_default_tracer(None)
    set_default_monitor(None)
    yield
    tune.set_default_cache(None)
    tune.set_active_cost_model(None)
    plan.set_default_registry(None)
    set_default_metrics(None)
    set_default_tracer(None)
    set_default_monitor(None)
