"""The comparison that decides ``correct``, on the CPU.

* A whole rehearsal run of each cell with the control (the plain
  reference at the step below float32: three bf16 passes) planted in the
  program's place comes out ``correct: false``, at the configurations'
  own channel widths (All-CNN-C at its own 32 px; VGG-16 on small
  images).
* A whole rehearsal run of each cell, with the timed path broken
  underneath (an answer altered where it is produced), comes out
  ``correct: false``; unbroken, ``correct: true``.  The inference cells
  have no optimizer step, no mean over a batch and no exchange between
  chips, so an altered answer is the one fault they can have.
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import control, harness  # noqa: E402
from bench import run as bench_run  # noqa: E402

CELLS = ("vgg16-fprop-b128", "allcnn-serve")
# images at the published channel widths: the reductions as long as in
# the cell, the images small enough for XLA on a CPU
CONTROL_HW = {"vgg16-fprop-b128": 16, "allcnn-serve": 32}


@pytest.fixture(autouse=True)
def _isolated_artifacts(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "tune.json"))
    monkeypatch.setenv("REPRO_CALIBRATION", str(tmp_path / "calib.json"))


@pytest.fixture(scope="module")
def bench():
    return harness.Bench(ROOT)


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("seed", [3, 2 ** 32 + 17])
def test_control_fails_the_limit(bench, workload, seed):
    config = bench.config(bench.workload(workload)["config"])
    with control.planted(bench.reference(config["reference"])):
        line = bench_run.run_cell(
            bench, workload, seed=seed, seconds=1.0, trace=False,
            rehearsal=True, rehearsal_size=(CONTROL_HW[workload], 0))
    assert line["correct"] is False, line["checks"]
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def _altered(execute):
    def run(self, a, b):
        out = execute(self, a, b)
        return out.at[(0,) * out.ndim].add(1.0)
    return run


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", [None, "answer_altered"])
def test_rehearsal_run_is_judged(bench, workload, fault, monkeypatch):
    if fault == "answer_altered":
        from repro.plan.build import ConvPlan
        monkeypatch.setattr(ConvPlan, "execute", _altered(ConvPlan.execute))
    line = bench_run.run_cell(
        bench, workload, seed=7, seconds=1.0, trace=False, rehearsal=True,
        rehearsal_size=(CONTROL_HW[workload], bench_run.REHEARSAL_CH))
    assert line["correct"] is (fault is None), line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 1
