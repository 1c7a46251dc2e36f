"""Render an observability artifact as a human (or machine) report.

Reads any artifact the obs layer or the profiler writes and prints what an
operator asks of the serving/tuning stack first — latency quantiles,
occupancy, padding waste, cost-model drift, and what the host did while
the device idled:

  metrics dump   ``MetricRegistry.dump(path)`` JSON ({"kind": "repro-obs"}),
                 optionally carrying a drift-monitor snapshot under "drift";
  trace export   ``Tracer.export(path)`` Chrome trace-event JSON
                 ({"traceEvents": [...]}) — per-span-name duration stats;
  profiler trace a ``jax.profiler`` ``.xplane.pb`` (or a directory holding
                 one, e.g. ``bench/run.py --trace-dir``) — device busy and
                 idle time over the ``bench.window`` span (else the extent
                 of the device's ops), every idle interval split by the
                 innermost ``repro.*`` span open at that instant.

Usage:

    PYTHONPATH=src python scripts/obsreport.py metrics.json
    PYTHONPATH=src python scripts/obsreport.py trace.json --json
    python scripts/obsreport.py <trace-dir or .xplane.pb>

``--json`` emits the computed report as one JSON document instead of text
(the same numbers, for CI assertions and dashboards).
"""
from __future__ import annotations

import argparse
import bisect
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(1, ROOT)

from bench import trace_reduce                         # noqa: E402
from repro.obs.metrics import summarize_histogram      # noqa: E402


# --------------------------------------------------------------------------
# metrics-dump report
# --------------------------------------------------------------------------
def _fmt_s(v: float) -> str:
    """Seconds, scaled to a readable unit."""
    if v >= 1.0:
        return f"{v:.2f}s"
    if v >= 1e-3:
        return f"{v * 1e3:.2f}ms"
    return f"{v * 1e6:.1f}us"


def metrics_report(doc: Dict) -> Dict:
    """Structured report from a ``repro-obs`` metrics dump."""
    metrics = doc.get("metrics", {})
    by_kind: Dict[str, Dict] = {"counter": {}, "gauge": {}, "histogram": {}}
    for name, entry in sorted(metrics.items()):
        kind = entry.get("type")
        if kind == "histogram":
            h = summarize_histogram(dict(entry))
            by_kind["histogram"][name] = {
                "count": h["count"], "mean": h["mean"], "p50": h["p50"],
                "p90": h["p90"], "p99": h["p99"],
                "min": h["min"], "max": h["max"]}
        elif kind in by_kind:
            by_kind[kind][name] = entry["value"]
    report: Dict = {"kind": "metrics", "counters": by_kind["counter"],
                    "gauges": by_kind["gauge"],
                    "histograms": by_kind["histogram"]}

    # serving derivations: the questions stats() answers, from raw counters
    c = by_kind["counter"]
    lanes = c.get("repro.serve.bucket_lanes", 0.0)
    occupied = c.get("repro.serve.occupied_lanes", 0.0)
    if lanes:
        occ = occupied / lanes
        report["serving"] = {
            "requests": c.get("repro.serve.requests", 0.0),
            "dispatches": c.get("repro.serve.dispatches", 0.0),
            "occupancy": occ,
            "pad_waste_pct": 100.0 * (1.0 - occ),
            "hook_errors": c.get("repro.serve.dispatch_hook_errors", 0.0),
        }

    # scheduler SLO derivations (repro.serve.sched): deadline health, shed
    # pressure, flush-reason mix, and the latency quantiles an operator
    # reads before reaching for a raw Perfetto trace
    h = by_kind["histogram"]
    if any(k in c for k in ("repro.serve.deadline_requests",
                            "repro.serve.shed_total",
                            "repro.serve.deadline_flushes")):
        dl = c.get("repro.serve.deadline_requests", 0.0)
        misses = c.get("repro.serve.deadline_misses", 0.0)
        slo: Dict = {
            "deadline_requests": dl,
            "deadline_misses": misses,
            "deadline_miss_rate": misses / dl if dl else 0.0,
            "shed_total": c.get("repro.serve.shed_total", 0.0),
            "flushes": {
                "deadline": c.get("repro.serve.deadline_flushes", 0.0),
                "occupancy": c.get("repro.serve.occupancy_flushes", 0.0),
                "gather_timeout": c.get(
                    "repro.serve.gather_timeout_flushes", 0.0),
            },
        }
        for label, name in (("queue_wait", "repro.serve.queue_wait_s"),
                            ("enqueue", "repro.serve.enqueue_s"),
                            ("poll", "repro.serve.poll_s"),
                            ("deadline_slack",
                             "repro.serve.deadline_slack_s")):
            if name in h:
                slo[label] = h[name]
        report["slo"] = slo

    drift = doc.get("drift")
    if drift:
        classes = drift.get("classes", {})
        report["drift"] = {
            "threshold": drift.get("threshold"),
            "classes": classes,
            "flagged": sorted(cl for cl, s in classes.items()
                              if s.get("flagged")),
        }
    return report


def print_metrics_report(report: Dict) -> None:
    if report["counters"]:
        print("== counters ==")
        for name, v in report["counters"].items():
            print(f"  {name:<42} {v:.0f}")
    if report["gauges"]:
        print("== gauges ==")
        for name, v in report["gauges"].items():
            print(f"  {name:<42} {v:g}")
    if report["histograms"]:
        print("== histograms ==")
        for name, h in report["histograms"].items():
            unit = _fmt_s if name.endswith("_s") else lambda v: f"{v:.3g}"
            print(f"  {name:<42} n={h['count']:<6.0f} "
                  f"mean={unit(h['mean'])} p50={unit(h['p50'])} "
                  f"p90={unit(h['p90'])} p99={unit(h['p99'])} "
                  f"max={unit(h['max'])}")
    if "serving" in report:
        s = report["serving"]
        print("== serving ==")
        print(f"  requests={s['requests']:.0f} "
              f"dispatches={s['dispatches']:.0f} "
              f"occupancy={s['occupancy']:.3f} "
              f"pad_waste={s['pad_waste_pct']:.1f}% "
              f"hook_errors={s['hook_errors']:.0f}")
    if "slo" in report:
        s = report["slo"]
        fl = s["flushes"]
        print("== slo (scheduler) ==")
        print(f"  deadline_requests={s['deadline_requests']:.0f} "
              f"misses={s['deadline_misses']:.0f} "
              f"miss_rate={s['deadline_miss_rate']:.3f} "
              f"shed={s['shed_total']:.0f}")
        print(f"  flushes: deadline={fl['deadline']:.0f} "
              f"occupancy={fl['occupancy']:.0f} "
              f"gather_timeout={fl['gather_timeout']:.0f}")
        for label in ("queue_wait", "enqueue", "poll", "deadline_slack"):
            if label in s:
                q = s[label]
                print(f"  {label:<16} n={q['count']:<6.0f} "
                      f"p50={_fmt_s(q['p50'])} p99={_fmt_s(q['p99'])}")
    if "drift" in report:
        d = report["drift"]
        print(f"== drift (threshold={d['threshold']}) ==")
        for cl, s in sorted(d["classes"].items()):
            flag = "  << FLAGGED" if s.get("flagged") else ""
            print(f"  {cl:<42} n={s['n']:<5} ewma_err={s['ewma_err']:.3f} "
                  f"last_err={s['last_err']:.3f}{flag}")
        if not d["classes"]:
            print("  (no observations)")


# --------------------------------------------------------------------------
# trace-export report
# --------------------------------------------------------------------------
def _percentile(sorted_vals: List[float], q: float) -> float:
    """Exact nearest-rank percentile over raw per-span durations."""
    if not sorted_vals:
        return 0.0
    i = min(int(q * len(sorted_vals)), len(sorted_vals) - 1)
    return sorted_vals[i]


def trace_report(doc: Dict) -> Dict:
    """Per-span-name duration stats from Chrome trace-event JSON."""
    events = [e for e in doc.get("traceEvents", [])
              if e.get("ph") == "X" and "dur" in e]
    by_name: Dict[str, List[float]] = {}
    span: Tuple[float, float] = (float("inf"), 0.0)
    for e in events:
        by_name.setdefault(e["name"], []).append(e["dur"] * 1e-6)
        span = (min(span[0], e["ts"]), max(span[1], e["ts"] + e["dur"]))
    spans = {}
    for name, durs in sorted(by_name.items()):
        durs.sort()
        spans[name] = {
            "count": len(durs), "total_s": sum(durs),
            "mean_s": sum(durs) / len(durs),
            "p50_s": _percentile(durs, 0.5),
            "p90_s": _percentile(durs, 0.9),
            "p99_s": _percentile(durs, 0.99),
            "max_s": durs[-1]}
    report = {"kind": "trace", "events": len(events),
              "dropped_events": doc.get("otherData", {}).get(
                  "dropped_events", 0),
              "wall_s": (span[1] - span[0]) * 1e-6 if events else 0.0,
              "spans": spans}
    # whole-model pipeline dispatches, per net: each group is one compiled
    # program over all its layers (the per-layer split of device time is
    # in the program's ``repro.serve.<layer>`` op names), so the host side
    # reads the model_dispatch span's ``layers`` and ``programs`` args
    nets: Dict[str, List[Dict]] = {}
    for e in events:
        args = e.get("args", {})
        if e["name"] == "repro.serve.model_dispatch" and "layers" in args:
            nets.setdefault(args.get("net", "?"), []).append(
                dict(args, dur=e["dur"] * 1e-6))
    if nets:
        per_net = {}
        for net, ds in sorted(nets.items()):
            durs = sorted(d["dur"] for d in ds)
            per_net[net] = {
                "count": len(ds), "layers": max(d["layers"] for d in ds),
                "programs_max": max(d["programs"] for d in ds),
                "mean_s": sum(durs) / len(durs),
                "p50_s": _percentile(durs, 0.5),
                "p99_s": _percentile(durs, 0.99), "max_s": durs[-1]}
        report["layers"] = per_net
    return report


def print_trace_report(report: Dict) -> None:
    print(f"== trace: {report['events']} spans over "
          f"{_fmt_s(report['wall_s'])} "
          f"(dropped={report['dropped_events']}) ==")
    for name, s in report["spans"].items():
        print(f"  {name:<34} n={s['count']:<6} total={_fmt_s(s['total_s'])} "
              f"mean={_fmt_s(s['mean_s'])} p50={_fmt_s(s['p50_s'])} "
              f"p90={_fmt_s(s['p90_s'])} p99={_fmt_s(s['p99_s'])} "
              f"max={_fmt_s(s['max_s'])}")
    if "layers" in report:
        print("== model dispatch (one chain program per group) ==")
        for net, s in report["layers"].items():
            print(f"  {net:<34} n={s['count']:<6} layers={s['layers']} "
                  f"programs<={s['programs_max']} "
                  f"mean={_fmt_s(s['mean_s'])} p50={_fmt_s(s['p50_s'])} "
                  f"p99={_fmt_s(s['p99_s'])} max={_fmt_s(s['max_s'])}")


# --------------------------------------------------------------------------
# profiler-trace report (.xplane.pb)
# --------------------------------------------------------------------------
PROGRAM_PREFIX = "repro."
NO_HOST_EVENT = "no host event"     # trace_reduce's label for the same
# spans that run only on the thread that drives the scheduler
LOOP_SPANS = ("repro.serve.step", "repro.serve.poll")


def _idle_intervals(busy: List[Tuple[float, float]], w0: float,
                    w1: float) -> List[Tuple[float, float]]:
    """The gaps of the window ``[w0, w1)`` between merged busy intervals."""
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    return [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]


def _attribute(idle: List[Tuple[float, float]], ranked) -> Dict[str, float]:
    """Split the idle intervals among ``ranked`` events, given in the order
    in which they claim time ((start, end, name), best first): each idle
    instant goes to the first event that covers it, the rest to
    ``NO_HOST_EVENT``.  Returns ns by name."""
    rem = list(idle)                   # unclaimed idle time, sorted, disjoint
    out: Dict[str, float] = {}
    for s, e, name in ranked:
        j = bisect.bisect_right(rem, s, key=lambda iv: iv[1])
        k = j
        pieces = []
        while k < len(rem) and rem[k][0] < e:
            a, b = rem[k]
            lo, hi = max(a, s), min(b, e)
            out[name] = out.get(name, 0.0) + (hi - lo)
            if a < lo:
                pieces.append((a, lo))
            if hi < b:
                pieces.append((hi, b))
            k += 1
        if k > j:
            rem[j:k] = pieces
    left = sum(b - a for a, b in rem)
    if left:
        out[NO_HOST_EVENT] = left
    return out


def xplane_report(planes) -> Dict:
    """Device busy and idle time of the first device over the window
    (``trace_reduce``'s: the ``bench.window`` span, else the extent of the
    device's ops), and every idle interval split by what the host was
    doing at that instant: the innermost ``repro.*`` span open on the
    serving loop's thread (the host line that runs ``repro.serve.step`` /
    ``repro.serve.poll`` longest), else the innermost on any thread, else
    the innermost runtime host event, else "no host event".  On one thread
    spans nest, so the innermost open span is the shortest that covers the
    instant."""
    planes = list(planes)
    devices = sorted(
        (p for p in planes if p.name.startswith(trace_reduce.DEVICE_PREFIX)),
        key=lambda p: int(p.name[len(trace_reduce.DEVICE_PREFIX):]))
    ops = [(ev.start_ns, ev.start_ns + ev.duration_ns)
           for p in devices[:1] for line in p.lines
           if line.name == trace_reduce.OPS_LINE for ev in line.events]
    if not ops:
        raise ValueError("the trace holds no device operation")
    lines = [(line.name, [(ev.start_ns, ev.start_ns + ev.duration_ns,
                           ev.name) for ev in line.events])
             for p in planes if p.name.startswith(trace_reduce.HOST_PLANE)
             for line in p.lines]
    w0, w1 = trace_reduce._window([ev for _, evs in lines for ev in evs],
                                  [(s, e, None, None) for s, e in ops])
    busy = trace_reduce._union([(max(s, w0), min(e, w1)) for s, e in ops
                                if e > w0 and s < w1])
    busy_s = sum(e - s for s, e in busy) * 1e-9
    idle = _idle_intervals(busy, w0, w1)

    def loop_time(evs):
        return sum(min(e, w1) - max(s, w0) for s, e, n in evs
                   if n in LOOP_SPANS and e > w0 and s < w1)
    loop_time_ns = [loop_time(evs) for _, evs in lines]
    loop = (max(range(len(lines)), key=loop_time_ns.__getitem__)
            if any(loop_time_ns) else None)
    ranked = []
    for i, (_, evs) in enumerate(lines):
        for s, e, n in evs:
            if n == trace_reduce.WINDOW_SPAN or e <= w0 or s >= w1:
                continue
            rank = (0 if i == loop else 1) if n.startswith(
                PROGRAM_PREFIX) else 2
            ranked.append((rank, e - s, s, e, n))
    ranked.sort()
    by_name = _attribute(idle, ((s, e, n) for _, _, s, e, n in ranked))
    idle_s = sum(by_name.values()) * 1e-9
    program_s = sum(v for n, v in by_name.items()
                    if n.startswith(PROGRAM_PREFIX)) * 1e-9
    return {
        "kind": "xplane",
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_s,
        "idle_s": idle_s,
        "idle_intervals": len(idle),
        "loop_thread": lines[loop][0] if loop is not None else None,
        "idle_by_span": {n: v * 1e-9 for n, v in
                         sorted(by_name.items(), key=lambda kv: -kv[1])},
        "unattributed_s": idle_s - program_s,
    }


def xplane_file(path: str) -> str:
    """``path`` itself, or the newest ``.xplane.pb`` under a directory."""
    if os.path.isdir(path):
        files = trace_reduce.trace_files(path)
        if not files:
            raise ValueError(f"no .xplane.pb under {path}")
        return max(files, key=os.path.getmtime)
    return path


def xplane_file_report(path: str) -> Dict:
    from jax.profiler import ProfileData
    report = xplane_report(ProfileData.from_file(xplane_file(path)).planes)
    report["path"] = path
    return report


def print_xplane_report(report: Dict) -> None:
    idle = report["idle_s"]
    share = (lambda v: 100.0 * v / idle) if idle else (lambda v: 0.0)
    print(f"== profiler trace: window {_fmt_s(report['window_s'])}, device "
          f"busy {_fmt_s(report['busy_s'])}, idle {_fmt_s(idle)} in "
          f"{report['idle_intervals']} intervals ==")
    print(f"  serving loop thread: {report['loop_thread']}")
    print(f"  unattributed (no repro.* span): "
          f"{_fmt_s(report['unattributed_s'])} "
          f"({share(report['unattributed_s']):.1f}%)")
    print("== device idle by host span ==")
    for name, v in report["idle_by_span"].items():
        print(f"  {name:<42} {_fmt_s(v):>10} {share(v):6.2f}%")


# --------------------------------------------------------------------------
# entry
# --------------------------------------------------------------------------
def build_report(doc: Dict) -> Dict:
    """Dispatch on artifact shape: metrics dump vs trace export."""
    if doc.get("kind") == "repro-obs":
        return metrics_report(doc)
    if "traceEvents" in doc:
        return trace_report(doc)
    raise ValueError(
        "unrecognized artifact: expected a MetricRegistry.dump() JSON "
        "(kind='repro-obs') or a Tracer.export() trace (traceEvents)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", help="metrics dump or exported trace JSON, or a "
                                 "profiler trace (.xplane.pb or its "
                                 "directory)")
    ap.add_argument("--json", action="store_true",
                    help="emit the report as JSON instead of text")
    args = ap.parse_args(argv)
    try:
        if os.path.isdir(args.path) or args.path.endswith(".xplane.pb"):
            report = xplane_file_report(args.path)
        else:
            with open(args.path) as f:
                report = build_report(json.load(f))
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if args.json:
        print(json.dumps(report, indent=1, sort_keys=True))
    else:
        {"metrics": print_metrics_report, "trace": print_trace_report,
         "xplane": print_xplane_report}[report["kind"]](report)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
