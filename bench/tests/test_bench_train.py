"""The training cell on the CPU: its work count, its trace reading, and the
comparison that decides ``correct`` in whole rehearsal runs.

* Unbroken, a rehearsal of ``resnet-train`` comes out ``correct: true``.
* It comes out ``correct: false`` with the control (the plain reference
  at three bf16 passes, in all three conv directions) planted in the
  program's place, with one weight gradient altered where it is produced,
  and with the AdamW update skipped.
"""
import collections
import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import control_train, harness, trace_reduce, trace_scopes  # noqa: E402
from bench import run as bench_run  # noqa: E402
from bench import work  # noqa: E402

CELL = "resnet-train"
# 32 px images (``shrink`` halves the cap for unchained layers), 16
# channels, and the loop's rehearsal floor of 128 images: batch norm's
# statistics in the 1x1 last stage are over the 128 samples of a channel
SIZE = (64, 16)

Plane = collections.namedtuple("Plane", "name lines")
Line = collections.namedtuple("Line", "name events")
Event = collections.namedtuple("Event", "name start_ns duration_ns stats")


@pytest.fixture(autouse=True)
def _isolated_artifacts(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "tune.json"))
    monkeypatch.setenv("REPRO_CALIBRATION", str(tmp_path / "calib.json"))


@pytest.fixture(scope="module")
def bench():
    return harness.Bench(ROOT)


def _loop(bench):
    return bench.loop(bench.traffic(bench.workload(CELL)["traffic"])["loop"])


def test_step_work_by_hand(bench):
    layers = [{"name": "stem", "IC": 3, "OC": 8, "in_hw": 8, "flt": 3,
               "pad": 1, "stride": 2},
              {"name": "s1b0.a", "IC": 8, "OC": 16, "in_hw": 4, "flt": 1,
               "pad": 0, "stride": 1}]
    peak = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    flops, least, dgrad = _loop(bench).step_work(layers, 2, 10, "float32",
                                                 peak)
    f0 = 2 * 2 * 8 * 4 * 4 * 3 * 9          # stem: B OC outH outW IC taps
    f1 = 2 * 2 * 16 * 4 * 4 * 8
    assert flops == 2 * f0 + 3 * f1 + 3 * 2 * 2 * 16 * 10
    b0 = 4 * (8 * 8 * 3 * 2 + 9 * 3 * 8 + 4 * 4 * 8 * 2)
    b1 = 4 * (4 * 4 * 8 * 2 + 8 * 16 + 4 * 4 * 16 * 2)
    t0, t1 = b0 / 1e9, b1 / 1e9             # both bound by memory here
    assert work.least_time(f0, b0, peak) == (t0, "memory")
    assert least == pytest.approx(2 * t0 + 3 * t1)
    assert dgrad == pytest.approx(t1)


HLO = """
  %fusion.7 = f32[4,4,8,2]{3,2,1,0} fusion(f32[4,4,8,2] %p), kind=kLoop, metadata={op_name="jit(train_step)/jvp(repro.graph.s1b0)/repro.graph.bn/mul" source_file="x.py"}
  %reduce-window.1 = f32[2,2,8,2]{3,2,1,0} reduce-window(f32[4,4,8,2] %a, f32[] %c), metadata={op_name="jit(train_step)/jvp(repro.graph.pool)/reduce_window_max"}
  %add.3 = f32[2,2,8,2]{3,2,1,0} add(f32[2,2,8,2] %x, f32[2,2,8,2] %y), metadata={op_name="jit(train_step)/transpose(jvp(repro.graph.s1b0))/repro.graph.add/add"}
  %dot.1 = f32[2,10]{1,0} dot(f32[2,8] %x, f32[8,10] %w), metadata={op_name="jit(train_step)/jvp(repro.graph.head)/dot_general"}
  %_exec_dgrad.4 = f32[4,4,8,2]{3,2,1,0} custom-call(f32[5,5,16,2] %a, f32[3,3,16,8] %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(repro.graph.s1b0))/jit(_exec_dgrad)/pallas_call"}
  %_exec_fprop.2 = f32[4,4,16,2]{3,2,1,0} custom-call(f32[4,4,8,2] %a, f32[1,1,8,16] %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(repro.graph.s1b0)/jit(_exec_fprop)/pallas_call"}
"""


def _event(hlo_line, s, e):
    return Event(hlo_line.split(", metadata=")[0].strip(), s, e - s, [])


def test_trace_scopes_hand_built():
    lines = [l for l in HLO.strip().splitlines()]
    ops = Line(trace_reduce.OPS_LINE, [
        _event(lines[0], 100, 150),       # bn: 50
        _event(lines[1], 150, 170),       # pool: 20
        _event(lines[2], 170, 180),       # add: 10
        _event(lines[3], 180, 200),       # head: 20, not graph_s
        _event(lines[4], 200, 300),       # dgrad kernel: 100
        _event(lines[5], 300, 340),       # fprop kernel: 40
        _event(lines[4], 900, 990),       # after the window
    ])
    planes = [Plane("/host:CPU", [Line("main", [
                  Event(trace_reduce.WINDOW_SPAN, 50, 750, [])])]),
              Plane("/device:TPU:0", [ops])]
    r = trace_scopes.reduce_planes(planes, trace_scopes.op_names(HLO))
    assert r["graph_s"] == pytest.approx(80e-9)
    assert r["scope_s"]["head"] == pytest.approx(20e-9)
    assert r["scope_s"]["s1b0"] == pytest.approx(140e-9)
    assert r["conv_dir_s"] == pytest.approx(
        {"fprop": 40e-9, "dgrad": 100e-9, "wgrad": 0.0})


def test_op_names_of_a_compiled_program():
    """The scope path reaches the compiled program's HLO text."""
    @jax.jit
    def f(x):
        with jax.named_scope("repro.graph.bn"):
            return jnp.sin(x) * 2.0
    names = trace_scopes.op_names(f.lower(jnp.ones((8, 8))).compile()
                                  .as_text())
    assert any(trace_scopes.scope(n) == "bn" for n in names.values())


def _wgrad_altered(execute):
    """The stem's filter gradient with one element moved by the tensor's
    largest magnitude."""
    def run(self, a, b):
        out = execute(self, a, b)
        if self.op.value == "wgrad" and self.scene.fltH == 7:   # the stem
            out = out.at[(0,) * out.ndim].add(jnp.max(jnp.abs(out)))
        return out
    return run


def _update_skipped(cfg, params, grads, state):
    return params, state, {"grad_norm": jnp.float32(0.0),
                           "lr": jnp.float32(0.0)}


@pytest.mark.parametrize("fault", [None, "control", "wgrad_altered",
                                   "update_skipped"])
def test_rehearsal_run_is_judged(bench, fault, monkeypatch):
    from repro.plan.build import ConvPlan
    from repro.train import optimizer

    if fault == "wgrad_altered":
        monkeypatch.setattr(ConvPlan, "execute",
                            _wgrad_altered(ConvPlan.execute))
    if fault == "update_skipped":
        monkeypatch.setattr(optimizer, "adamw_update", _update_skipped)

    def run():
        return bench_run.run_cell(bench, CELL, seed=2 ** 32 + 7, seconds=1.0,
                                  trace=False, rehearsal=True,
                                  rehearsal_size=SIZE)

    if fault == "control":
        cfg = bench.config(bench.workload(CELL)["config"])
        with control_train.planted(bench.reference(cfg["reference"])):
            line = run()
    else:
        line = run()
    assert line["correct"] is (fault is None), line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 1
