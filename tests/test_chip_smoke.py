"""The chip entry points off the chip: ``chip_smoke.py`` refuses to report
a result without a TPU and passes its CPU rehearsal, and the persistent
compilation cache goes where ``repro.launch.compile_cache`` says."""
import importlib.util
import json
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def cache_config(monkeypatch, tmp_path):
    """Persistent cache into tmp_path; JAX's cache settings restored after."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "jax"))
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    cc.reset_cache()
    yield tmp_path / "jax"
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])
    cc.reset_cache()


def test_smoke_without_tpu_exits_nonzero_and_prints_no_result(smoke,
                                                              capsys):
    assert smoke.main([]) == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU" in out.err


def test_smoke_cpu_rehearsal_passes_without_reporting_ok(smoke, capsys,
                                                         cache_config):
    assert smoke.main(["--cpu-rehearsal"]) == 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out
    last = json.loads(out.strip().splitlines()[-1])
    assert last["rehearsal"] == "passed"
    assert last["device"]["platform"] == "cpu"
    assert "serve: ok" in out and "train: ok" in out
    assert "reference plans 0" in out


def test_compile_cache_dir_honours_env(monkeypatch):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/jax")
    assert compile_cache.compile_cache_dir() == "/somewhere/jax"


def test_compile_cache_dir_defaults_to_checkout(monkeypatch, tmp_path):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    monkeypatch.chdir(tmp_path)          # not relative to the working dir
    assert compile_cache.compile_cache_dir() == str(REPO / ".cache" / "jax")


def test_enable_compile_cache_configures_jax(cache_config):
    assert compile_cache.enable_compile_cache() == str(cache_config)
    assert jax.config.jax_compilation_cache_dir == str(cache_config)
    assert (jax.config.jax_persistent_cache_min_compile_time_secs
            == compile_cache.MIN_COMPILE_TIME_S)
