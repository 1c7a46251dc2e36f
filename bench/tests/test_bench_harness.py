"""The harness on the CPU: parts found by name, no result off the chip, the
yardstick's work counts, and BENCHMARK.json's shape."""
import json
import math
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import common, harness, work  # noqa: E402
from bench import run as bench_run  # noqa: E402

# A loop of its own, added as a file: a fixed number of passes, each layer
# planned and run once a pass, the last pass compared with the reference.
NEW_LOOP = """
import time
import jax
from bench import common


def rehearsal(traffic, batch):
    return dict(traffic, batch=min(traffic["batch"], batch))


def run(ctx):
    from repro.plan import make_plan
    cfg, batch = ctx.config, ctx.traffic["batch"]
    layers = cfg["layers"]
    scenes = [common.scene_of(l, batch, cfg["dtype"]) for l in layers]
    k_w, k_x = jax.random.split(common.seed_key(ctx.seed))
    ws = common.he_weights(k_w, layers, cfg["dtype"])
    xs = common.normal_arrays(k_x, tuple(s.in_shape() for s in scenes),
                              cfg["dtype"])
    plans = [make_plan(s) for s in scenes]
    t0 = time.perf_counter()
    for _ in range(ctx.traffic["passes"]):
        outs = jax.block_until_ready(
            [p.execute(x, w) for p, x, w in zip(plans, xs, ws)])
    checks = {}
    for i, layer in enumerate(layers):
        want = ctx.reference.layer_output(cfg, i, xs[i], ws[i], "highest")
        common.check(checks, layer["name"] + "_rel_err",
                     common.rel_err(outs[i], want),
                     cfg["correct"]["max_rel_err"])
    n = ctx.traffic["passes"]
    return {"setup_s": t0 - ctx.t0, "window_s": time.perf_counter() - t0,
            "attempted": n, "failed": 0, "passes": n,
            "memory_peak_bytes": None, "checks": checks}
"""

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(autouse=True)
def _isolated_artifacts(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "tune.json"))
    monkeypatch.setenv("REPRO_CALIBRATION", str(tmp_path / "calib.json"))


def _copy_bench(dst):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))


def test_new_config_traffic_and_metric_are_found_from_new_files(tmp_path):
    _copy_bench(tmp_path)
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "vgg16-fig13.json").read_text())
    cfg["name"] = "vgg16-head"
    cfg["layers"] = cfg["layers"][:2]
    (b / "configs" / "vgg16-head.json").write_text(json.dumps(cfg))
    (b / "loops" / "fixed_passes.py").write_text(NEW_LOOP)
    (b / "traffic" / "fprop-pair.json").write_text(json.dumps(
        {"loop": "fixed_passes", "batch": 2, "passes": 3}))
    (b / "metrics" / "passes_done.py").write_text(
        "def read(rec):\n    return float(rec['passes'])\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "vgg16-head.pair",
                              "config": "vgg16-head",
                              "traffic": "fprop-pair", "chips": 1,
                              "why": "test cell"})
    spec["end_to_end"].append({"name": "passes_done", "unit": "passes",
                               "better": "higher", "bound": 0.1,
                               "source": "host_clock",
                               "workloads": ["vgg16-head.pair"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    line = bench_run.run_cell(harness.Bench(str(tmp_path)), "vgg16-head.pair",
                              seed=2 ** 33 + 11, seconds=0.2, trace=False,
                              rehearsal=True)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"setup_s", "passes_done"}
    assert line["metrics"]["passes_done"]["value"] == line["attempted"] == 3
    assert set(line["checks"]) == {"L0_rel_err", "L1_rel_err"}
    assert list(line)[-1] == "checks"


def test_no_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "vgg16-fprop-b128",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_without_the_program_exits_nonzero(tmp_path):
    _copy_bench(tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "vgg16-fprop-b128",
         "--seed", "1", "--seconds", "1", "--trace", "0", "--cpu-rehearsal"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def _layer(config, name):
    cfg = json.load(open(os.path.join(ROOT, "bench", "configs",
                                      config + ".json")))
    return next(l for l in cfg["layers"] if l["name"] == name)


def test_work_counts_match_hand_arithmetic():
    # VGG-16 L1: 224x224, 64 -> 64, 3x3, pad 1, at B=128.
    l1 = _layer("vgg16-fig13", "L1")
    assert work.layer_flops(l1, 128) == 2 * 128 * 64 * 224 * 224 * 64 * 9
    assert work.layer_flops(l1, 128) == 473_520_144_384
    # in 224*224*64*128 + flt 3*3*64*64 + out 224*224*64*128, 4 bytes each
    assert work.layer_bytes(l1, 128) == 3_288_481_792
    # All-CNN-C L2: 32x32, 96 -> 96, 3x3 stride 2 pad 1, at B=1.
    l2 = _layer("allcnn-c", "L2")
    assert work.out_hw(l2) == 16
    assert work.layer_flops(l2, 1) == 2 * 96 * 16 * 16 * 96 * 9
    assert work.layer_flops(l2, 1) == 42_467_328
    # in 98,304 + flt 82,944 + out 24,576 elements
    assert work.layer_bytes(l2, 1) == 823_296
    # L6, unpadded: 8x8 -> 6x6
    assert work.out_hw(_layer("allcnn-c", "L6")) == 6


def test_peaks_and_least_time():
    peak = work.peaks("TPU v5 lite")
    assert peak["flops_per_s"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
    t, bound = work.least_time(197e12, 1.0, peak)
    assert (t, bound) == (1.0, "compute")
    t, bound = work.least_time(1.0, 819e9, peak)
    assert (t, bound) == (1.0, "memory")


def test_arrivals_are_one_set_of_gaps_in_seed_order():
    loops = harness.Bench(ROOT).loop("open_poisson")
    a = loops.arrival_gaps(2.5, 40, seed=1)
    b = loops.arrival_gaps(2.5, 40, seed=2 ** 31 + 7)
    assert len(a) == len(b) == 100
    assert list(a) != list(b)
    assert sorted(a) == sorted(b)
    assert abs(a.mean() - 1 / 2.5) < 0.01
    offsets = loops.arrival_offsets(2.5, 40, seed=1)
    assert offsets[0] == 0.0 and offsets[-1] < 40


def test_large_seeds_give_distinct_keys():
    import jax
    k1 = common.seed_key(5)
    k2 = common.seed_key(2 ** 33 + 5)
    assert not bool((jax.random.key_data(k1) == jax.random.key_data(k2))
                    .all())


def test_suffixed_metric_falls_back_to_its_base_reader(tmp_path):
    _copy_bench(tmp_path)
    b = harness.Bench(str(tmp_path))
    assert b.reader("conv_roofline.serve") is b.reader("conv_roofline")
    assert b.reader("mfu.serve") is not b.reader("mfu")
    (tmp_path / "bench" / "metrics" / "device_idle.serve.py").write_text(
        "def read(rec):\n    return 1.0\n")
    assert b.reader("device_idle.serve").read({}) == 1.0
    with pytest.raises(FileNotFoundError):
        b.reader("no_such_metric.serve")


def test_gc_log_records_collections():
    import gc
    with common.GcLog() as log:
        gc.collect()
    s = log.summary()
    assert s["gc_collections"] >= 1 and s["gc_gen2_collections"] >= 1
    assert 0 <= s["gc_pause_max_s"] <= s["gc_pause_sum_s"]


def test_percentile_counts_failures_beyond_every_latency():
    lat = [0.1] * 19 + [math.inf]
    assert harness.percentile(lat, 0.5) == 0.1
    assert harness.percentile(lat, 0.95) == 0.1
    assert harness.percentile(lat + [math.inf], 0.95) == math.inf


def test_benchmark_json_keeps_the_contract():
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["bench"]
    assert 1 <= spec["run_seconds"] <= 51
    b = harness.Bench(ROOT)
    names = set()
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert b.config(c["name"])["name"] == c["name"]
        names.add(c["name"])
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in names and w["chips"] in (1, 4)
        loop = b.loop(b.traffic(w["traffic"])["loop"])
        assert callable(loop.run) and callable(loop.rehearsal)
        got = {m["name"] for m in b.metrics_for(w["name"], False)}
        assert "setup_s" in got and len(got) >= 2
        assert b.metrics_for(w["name"], True)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]), m["name"]
        assert m["better"] in ("lower", "higher")
        assert callable(b.reader(m["name"]).read)
        if m in spec["per_layer"]:
            assert m["moves"] in e2e
        else:
            assert 0.01 <= m["bound"] <= 0.25
    for w in spec["workloads"]:
        assert NAME.match(w["name"])
