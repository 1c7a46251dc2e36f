"""Finds the parts of a cell by the names in ``BENCHMARK.json`` and turns a
run's record into the result line.

    bench/configs/<config>.json       layer geometry, source, cuts, limits
    bench/traffic/<traffic>.json      the loop that drives it, and its
                                      parameters
    bench/loops/<loop>.py             ``run(ctx) -> record`` and
                                      ``rehearsal(traffic, batch)``
    bench/references/<name>.py        the plain reference a config names
    bench/metrics/<metric>.py         ``read(record) -> float | None``; a
                                      metric ``<base>.<suffix>`` with no
                                      file of its own is read by
                                      ``<base>.py``

A later cell, traffic mix, loop or metric is a new file and a new entry;
no file here names one.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
from typing import Optional

BENCH_DIRNAME = "bench"


def _load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{path} does not exist")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """``BENCHMARK.json`` at ``root`` and the files under ``root/bench``."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        self.dir = os.path.join(self.root, BENCH_DIRNAME)
        with open(os.path.join(self.root, "BENCHMARK.json")) as f:
            self.spec = json.load(f)
        self._modules = {}

    def _module(self, sub: str, name: str):
        """``bench/<sub>/<name>.py``, loaded once."""
        key = (sub, name)
        if key not in self._modules:
            self._modules[key] = _load_module(
                os.path.join(self.dir, sub, name + ".py"),
                f"bench_{sub}_{name.replace('.', '_').replace('-', '_')}")
        return self._modules[key]

    def _json(self, sub: str, name: str) -> dict:
        with open(os.path.join(self.dir, sub, name + ".json")) as f:
            return json.load(f)

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"unknown workload {name!r}; have "
                       f"{[w['name'] for w in self.spec['workloads']]}")

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def loop(self, name: str):
        return self._module("loops", name)

    def reference(self, name: str):
        return self._module("references", name)

    def reader(self, metric: str):
        """The metric's own reader, else that of its name without the last
        ``.<suffix>`` (``conv_roofline.serve`` is read by
        ``conv_roofline.py`` when it has no file of its own)."""
        own = os.path.join(self.dir, "metrics", metric + ".py")
        if not os.path.isfile(own) and "." in metric:
            return self.reader(metric.rsplit(".", 1)[0])
        return self._module("metrics", metric)

    def metrics_for(self, workload: str, trace: bool) -> list:
        """The cell's end-to-end metrics (``trace=False``) or its per-layer
        metrics (``trace=True``)."""
        group = self.spec["per_layer" if trace else "end_to_end"]
        return [m for m in group
                if workload in m.get("workloads", [workload])]

    def read_metrics(self, workload: str, trace: bool, record: dict) -> dict:
        out = {}
        for m in self.metrics_for(workload, trace):
            value = self.reader(m["name"]).read(record)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out


def percentile(values, q: float) -> float:
    """Nearest-rank ``q``-quantile (0 < q <= 1) of every value, infinite
    ones (failed requests) included."""
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


def judge(checks: dict) -> bool:
    """Correct when every number compared is finite and within its limit."""
    return bool(checks) and all(math.isfinite(v) and v <= lim
                                for v, lim in checks.values())


def result(record: dict, metrics: dict, device: dict,
           breakdown: Optional[dict] = None) -> dict:
    """The result line; ``checks`` comes last, each number by its limit."""
    line = {"correct": judge(record["checks"]),
            "attempted": record["attempted"], "failed": record["failed"],
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in record["checks"].items()}
    return line
