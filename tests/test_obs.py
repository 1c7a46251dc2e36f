"""Observability layer: thread-safe metrics, span tracing on the profiler's
clock with Chrome-trace export, cost-model drift flagging, the profiler-trace
report, and the serving integration — direct ``DispatchRecord`` emission,
hook-error containment, windowed stats, no device sync under tracing, and
the no-span-allocation guarantee of the untraced hot path."""
import collections
import glob
import importlib.util
import json
import math
import os
import threading

import jax
import jax.numpy as jnp
import pytest

import repro.obs.trace as trace_mod
from repro.core.mapping import ai_band, class_key, select_schedule
from repro.core.scene import ConvScene
from repro.obs import (DriftMonitor, MetricRegistry, Tracer, default_metrics,
                       default_monitor, profiler_recording, scene_class,
                       set_default_tracer, snapshot_delta, snapshot_value)
from repro.obs.metrics import (DEFAULT_RATIO_BUCKETS, histogram_percentile,
                               summarize_histogram)
from repro.serve import (ConvRequest, ConvScheduler, SchedConfig,
                         server_from_scenes)
from repro.tune.autotune import error_summary

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


def _load_script(name):
    path = os.path.join(os.path.dirname(__file__), "..", "scripts",
                        f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


TINY = ConvScene(B=1, IC=4, OC=4, inH=6, inW=6, fltH=3, fltW=3,
                 padH=1, padW=1)


def _server(**kwargs):
    kwargs.setdefault("max_batch", 4)
    kwargs.setdefault("ladder_slack", 0.0)
    server = server_from_scenes({"l0": TINY}, **kwargs)
    server.prewarm()
    return server


def _reqs(n, b=1, seed=0):
    return [ConvRequest(rid=i, layer="l0",
                        x=jax.random.normal(jax.random.PRNGKey(seed + i),
                                            (TINY.inH, TINY.inW, TINY.IC, b),
                                            jnp.float32))
            for i in range(n)]


# -- metrics -----------------------------------------------------------------
def test_metric_kinds_and_name_scheme():
    m = MetricRegistry()
    with pytest.raises(ValueError, match="scheme"):
        m.counter("NotDotted")
    with pytest.raises(ValueError, match="scheme"):
        m.counter("nodots")
    c = m.counter("repro.test.c")
    c.inc()
    c.inc(2.5)
    assert m.value("repro.test.c") == 3.5
    with pytest.raises(ValueError, match="decrease"):
        c.inc(-1)
    m.gauge("repro.test.g").set(7)
    assert m.value("repro.test.g") == 7.0
    # a name is permanently typed: re-registering as another kind raises
    with pytest.raises(ValueError, match="already registered"):
        m.gauge("repro.test.c")
    h = m.histogram("repro.test.h_s")
    with pytest.raises(ValueError, match="different"):
        m.histogram("repro.test.h_s", bounds=(1.0, 2.0))
    h.observe(0.5)
    h.observe(math.inf)   # non-finite samples are ignored, never poison sum
    h.observe(math.nan)
    assert h.count == 1
    assert m.names() == ["repro.test.c", "repro.test.g", "repro.test.h_s"]


def test_threaded_counter_and_histogram_correctness():
    m = MetricRegistry()
    c = m.counter("repro.test.n")
    h = m.histogram("repro.test.lat_s")
    threads, per = 8, 1000

    def work(k):
        for i in range(per):
            c.inc()
            h.observe((i % 100 + 1) * 1e-4)

    ts = [threading.Thread(target=work, args=(k,)) for k in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == threads * per
    snap = h._snapshot()
    assert snap["count"] == threads * per
    assert sum(snap["counts"]) == threads * per
    assert snap["sum"] == pytest.approx(threads * per * 50.5e-4, rel=1e-6)


def test_histogram_percentiles_and_overflow():
    m = MetricRegistry()
    h = m.histogram("repro.test.d", bounds=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 1.5, 3.0):
        h.observe(v)
    snap = summarize_histogram(h._snapshot())
    assert snap["min"] == 0.5 and snap["max"] == 3.0
    assert 1.0 <= snap["p50"] <= 2.0, "median falls in the (1, 2] bucket"
    # everything beyond the last bound lands in the overflow bucket, whose
    # quantile estimate is the observed max
    h2 = m.histogram("repro.test.o", bounds=(1.0,))
    h2.observe(100.0)
    assert h2.percentile(0.99) == 100.0
    with pytest.raises(ValueError, match="quantile"):
        histogram_percentile(snap, 1.5)


def test_snapshot_delta_and_reset():
    m = MetricRegistry()
    c, h = m.counter("repro.test.c"), m.histogram("repro.test.h")
    g = m.gauge("repro.test.depth")
    c.inc(5)
    h.observe(1e-3)
    before = m.snapshot()
    c.inc(2)
    h.observe(2e-3)
    h.observe(3e-3)
    g.set(9)
    win = snapshot_delta(before, m.snapshot())
    assert snapshot_value(win, "repro.test.c") == 2.0
    assert win["repro.test.h"]["count"] == 2
    assert win["repro.test.h"]["sum"] == pytest.approx(5e-3)
    assert win["repro.test.depth"]["value"] == 9.0, "gauges keep the level"
    # a metric born after `before` counts from zero
    m.counter("repro.test.new").inc(4)
    win2 = snapshot_delta(before, m.snapshot())
    assert snapshot_value(win2, "repro.test.new") == 4.0
    m.reset()
    assert m.value("repro.test.c") == 0.0
    assert m.names(), "reset keeps registrations"


def test_dump_and_obsreport_metrics(tmp_path):
    m = MetricRegistry()
    m.counter("repro.serve.requests").inc(10)
    m.counter("repro.serve.dispatches").inc(4)
    m.counter("repro.serve.occupied_lanes").inc(10)
    m.counter("repro.serve.bucket_lanes").inc(16)
    m.histogram("repro.serve.enqueue_s").observe(2e-3)
    mon = DriftMonitor(threshold=0.5, min_samples=1,
                       metrics=MetricRegistry())
    mon.observe("TB88|compute|hi", 1.0, 10.0)
    p = m.dump(str(tmp_path / "metrics.json"),
               extra={"drift": mon.snapshot()})
    doc = json.loads(open(p).read())
    assert doc["kind"] == "repro-obs"
    report = _load_script("obsreport").build_report(doc)
    assert report["serving"]["occupancy"] == pytest.approx(10 / 16)
    assert report["serving"]["pad_waste_pct"] == pytest.approx(100 * 6 / 16)
    assert report["drift"]["flagged"] == ["TB88|compute|hi"]
    assert report["histograms"]["repro.serve.enqueue_s"]["count"] == 1


# -- tracing -----------------------------------------------------------------
def test_span_nesting_and_chrome_trace_export(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("repro.test.outer", k=1):
        assert tr.current() == "repro.test.outer"
        with tr.span("repro.test.inner"):
            assert tr.current() == "repro.test.inner"
    assert tr.current() is None
    with pytest.raises(RuntimeError):
        with tr.span("repro.test.fails"):
            raise RuntimeError("boom")
    events = tr.events()
    names = [e["name"] for e in events]
    # spans record on exit: inner finishes before outer
    assert names == ["repro.test.inner", "repro.test.outer",
                     "repro.test.fails"]
    by = {e["name"]: e for e in events}
    assert by["repro.test.inner"]["args"]["parent"] == "repro.test.outer"
    assert by["repro.test.fails"]["args"]["error"] == "RuntimeError"

    p = tr.export(str(tmp_path / "trace.json"))
    doc = json.loads(open(p).read())   # valid JSON is the Perfetto contract
    assert doc["displayTimeUnit"] == "ms"
    for e in doc["traceEvents"]:
        assert e["ph"] == "X"
        assert {"name", "ts", "dur", "pid", "tid", "args"} <= set(e)
        assert e["dur"] >= 0
    report = _load_script("obsreport").build_report(doc)
    assert report["spans"]["repro.test.inner"]["count"] == 1


def test_tracer_disabled_is_shared_noop_and_decorator():
    tr = Tracer(enabled=False)
    s1, s2 = tr.span("repro.test.a"), tr.span("repro.test.b", k=1)
    assert s1 is s2 is trace_mod._NOOP, "disabled path allocates nothing"
    with s1 as sp:
        sp.set(any="thing")
    assert len(tr) == 0

    calls = []
    tr.enabled = True

    @tr.traced("repro.test.fn")
    def fn(x):
        calls.append(x)
        return x * 2

    assert fn(3) == 6
    assert [e["name"] for e in tr.events()] == ["repro.test.fn"]


def test_span_stream_subscribers_and_ring_buffer():
    """The span stream is the buffer alone: there are no subscribers (the
    serving layer publishes its records directly), and the ring buffer
    keeps the newest spans, counting what fell off."""
    assert not hasattr(Tracer, "subscribe")
    tr = Tracer(enabled=True, max_events=3)
    for i in range(5):
        with tr.span("repro.test.s", i=i) as sp:
            sp.set(square=i * i)
    events = tr.events()
    assert [e["args"]["i"] for e in events] == [2, 3, 4]
    assert [e["args"]["square"] for e in events] == [4, 9, 16]
    assert all(e["dur"] >= 0 for e in events)
    # exported ts is µs on the wall clock, in start order
    assert [e["ts"] for e in events] == sorted(e["ts"] for e in events)
    assert tr.dropped_events == 2
    tr.clear()
    assert len(tr) == 0 and tr.dropped_events == 0


def _xplane(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                        recursive=True)
    pd = ProfileData.from_file(path)
    start = next(dict(p.stats)["profile_start_time"] for p in pd.planes
                 if p.name == "Task Environment")
    host = [(i, ev) for p in pd.planes if p.name.startswith("/host:CPU")
            for i, line in enumerate(p.lines) for ev in line.events]
    return start, host


@pytest.mark.parametrize("enabled", [True, False])
def test_span_lands_on_profiler_host_plane(tmp_path, enabled):
    """While a profiler session records, a span is a host event of the
    trace on the calling thread, with its name and args, whether or not
    the tracer buffers it; a buffered span's exported ts is the event's
    start on the same clock (profile start + offset) to within 50 µs."""
    tr = Tracer(enabled=enabled)
    assert not profiler_recording()

    def worker():
        with tr.span("repro.test.worker", k=2):
            with tr.span("repro.test.inner"):
                pass

    jax.profiler.start_trace(str(tmp_path))
    try:
        assert profiler_recording()
        with tr.span("repro.test.main", k=1, tag="x") as sp:
            sp.set(late=7)
            th = threading.Thread(target=worker)
            th.start()
            th.join(timeout=30)
    finally:
        jax.profiler.stop_trace()
    assert not th.is_alive()
    assert not profiler_recording()
    start, host = _xplane(tmp_path)
    ev = {e.name: (line, e) for line, e in host
          if e.name.startswith("repro.test.")}
    assert set(ev) == {"repro.test.main", "repro.test.worker",
                       "repro.test.inner"}
    assert dict(ev["repro.test.main"][1].stats) == {"k": 1, "tag": "x",
                                                    "late": 7}
    assert dict(ev["repro.test.worker"][1].stats) == {"k": 2}
    # the thread: the worker's spans share a line that is not main's
    assert ev["repro.test.worker"][0] == ev["repro.test.inner"][0]
    assert ev["repro.test.worker"][0] != ev["repro.test.main"][0]
    if not enabled:
        assert len(tr) == 0
        return
    exported = {e["name"]: e for e in tr.events()}
    for name, (_, e) in ev.items():
        t_ns = start + e.start_ns
        assert abs(exported[name]["ts"] * 1e3 - t_ns) < 50e3, name
        assert abs(exported[name]["dur"] * 1e3 - e.duration_ns) < 50e3, name


# -- drift -------------------------------------------------------------------
def test_drift_flags_injected_mispredictions():
    mon = DriftMonitor(alpha=0.5, threshold=0.5, min_samples=3,
                       metrics=MetricRegistry())
    # well-predicted class: never flags
    for _ in range(5):
        mon.observe("good", 1.0e-3, 1.1e-3)
    # mispredicted class (10x off): flags only once min_samples is reached
    assert mon.observe("bad", 1.0e-3, 1.0e-2) == pytest.approx(0.9)
    mon.observe("bad", 1.0e-3, 1.0e-2)
    assert mon.flagged() == [], "below min_samples nothing pages"
    mon.observe("bad", 1.0e-3, 1.0e-2)
    assert mon.flagged() == ["bad"]
    st = mon.stats()["bad"]
    assert st.n == 3 and st.flagged and st.ewma_err > 0.5
    assert not mon.stats()["good"].flagged
    snap = mon.snapshot()
    assert snap["classes"]["bad"]["flagged"] is True
    mon.reset()
    assert mon.stats() == {} and mon.flagged() == []


def test_drift_drops_nonfinite_pairs():
    m = MetricRegistry()
    mon = DriftMonitor(metrics=m)
    assert mon.observe("c", 1.0, math.inf) is None
    assert mon.observe("c", math.nan, 1.0) is None
    assert mon.observe("c", 1.0, 0.0) is None, "zero measured: undefined err"
    assert mon.stats() == {}
    assert m.value("repro.drift.dropped") == 3.0
    assert m.value("repro.drift.observations") == 0.0


def test_scene_class_matches_calibration_bucket():
    ch = select_schedule(TINY)
    assert scene_class(TINY, ch) == class_key(
        ch.schedule, ch.bound, ai_band(TINY.arithmetic_intensity))


def test_error_summary_excludes_nonfinite():
    es = error_summary([0.1, 0.3, math.inf, math.nan])
    assert es["n"] == 4 and es["n_finite"] == 2 and es["n_nonfinite"] == 2
    assert es["mean"] == pytest.approx(0.2) and es["max"] == 0.3
    assert math.isnan(error_summary([])["mean"])


# -- serving integration -----------------------------------------------------
def test_traced_burst_spans_records_and_drift(tmp_path):
    """A traced burst spans every stage (submit, step, dispatch, glue on
    both sides, the plan's execute), the dispatch spans agree with the
    published records, and serving feeds no drift monitor: without a
    device sync there is no kernel time to audit the cost model with."""
    tr = Tracer(enabled=True)
    set_default_tracer(tr)     # the plan's spans go to the default tracer
    records = []
    server = _server(tracer=tr, on_dispatch=records.append)
    outs = server.serve(_reqs(6))
    assert len(outs) == 6
    spans = [e for e in tr.events() if e["name"] == "repro.serve.dispatch"]
    assert len(spans) == len(records) >= 1
    assert sum(r.requests for r in records) == 6
    assert [(e["args"]["bucket"], e["args"]["occupied"],
             e["args"]["requests"]) for e in spans] == [
        (r.bucket, r.occupied, r.requests) for r in records]
    count = collections.Counter(e["name"] for e in tr.events())
    assert count["repro.serve.submit"] == 6
    assert count["repro.serve.step"] == len(spans) + 1   # + the empty one
    assert count["repro.serve.glue"] == 2 * len(spans)
    assert count["repro.plan.execute"] == len(spans)
    by_parent = {e["name"]: e["args"].get("parent") for e in tr.events()}
    assert by_parent["repro.serve.dispatch"] == "repro.serve.step"
    assert by_parent["repro.plan.execute"] == "repro.serve.dispatch"
    assert sum(s.n for s in default_monitor().stats().values()) == 0
    assert not hasattr(server, "drift")
    # the exported trace parses and covers the dispatch spans
    doc = json.loads(open(tr.export(str(tmp_path / "t.json"))).read())
    assert len([e for e in doc["traceEvents"]
                if e["name"] == "repro.serve.dispatch"]) == len(spans)
    s = server.stats()
    assert s["requests"] == 6 and s["dispatches"] == len(records)


def test_two_traced_servers_do_not_cross_publish():
    """Records are published by the server that dispatched, not by a span
    stream two servers share."""
    tr = Tracer(enabled=True)
    rec_a, rec_b = [], []
    a = _server(tracer=tr, on_dispatch=rec_a.append)
    b = _server(tracer=tr, on_dispatch=rec_b.append)
    a.serve(_reqs(2))
    b.serve(_reqs(3))
    assert sum(r.requests for r in rec_a) == 2
    assert sum(r.requests for r in rec_b) == 3
    dispatches = [e for e in tr.events()
                  if e["name"] == "repro.serve.dispatch"]
    assert len(dispatches) == len(rec_a) + len(rec_b)


def _sched_net(tracer, records):
    """A two-layer chain behind a scheduler that flushes every 4 lanes."""
    s1 = TINY.with_batch(1)
    sched = ConvScheduler(max_batch=4, ladder_slack=0.0, tracer=tracer,
                          on_dispatch=records.append,
                          config=SchedConfig(occupancy_target=4))
    sched.register_net("net", {"a": s1, "b": s1}, activation=jax.nn.relu)
    sched.prewarm()
    return sched


@pytest.mark.parametrize("mode", ["enabled", "profiler"])
def test_tracing_never_blocks_dispatch(tmp_path, monkeypatch, mode):
    """With the tracer enabled, or a profiler session recording, neither
    dispatch path waits on the device, and the published DispatchRecords
    are those of an untraced run."""
    calls = []
    real = jax.block_until_ready

    def counting(x):
        calls.append(1)
        return real(x)

    def run(tracer):
        records = []
        server = _server(tracer=tracer, on_dispatch=records.append)
        sched = _sched_net(tracer, records)
        monkeypatch.setattr(jax, "block_until_ready", counting)
        try:
            server.serve(_reqs(6))
            sess = sched.session("net")
            sess.serve([r.x for r in _reqs(8, seed=20)])
        finally:
            monkeypatch.setattr(jax, "block_until_ready", real)
        return records

    untraced = run(Tracer(enabled=False))
    assert calls == []
    tr = Tracer(enabled=mode == "enabled")
    set_default_tracer(tr)     # the plan's spans go to the default tracer
    if mode == "profiler":
        jax.profiler.start_trace(str(tmp_path))
    try:
        traced = run(tr)
    finally:
        if mode == "profiler":
            jax.profiler.stop_trace()
    assert calls == [], "a traced dispatch waited on the device"
    assert traced == untraced
    assert {r.layer for r in traced} == {"l0", "@net"}
    # a model group is one model_dispatch span over both layers, with at
    # most three host enqueues; no per-layer host span is left
    groups = sum(r.layer == "@net" for r in traced)
    if mode == "enabled":
        names = {e["name"] for e in tr.events()}
        assert {"repro.serve.model_dispatch", "repro.serve.dispatch",
                "repro.serve.glue", "repro.plan.execute"} <= names
        assert "repro.serve.layer_dispatch" not in names
        model = [e["args"] for e in tr.events()
                 if e["name"] == "repro.serve.model_dispatch"]
    else:
        _, host = _xplane(tmp_path)
        assert not any(e.name == "repro.serve.layer_dispatch"
                       for _, e in host)
        model = [dict(e.stats) for _, e in host
                 if e.name == "repro.serve.model_dispatch"]
    assert len(model) == groups >= 1
    assert all(a["layers"] == 2 and 1 <= a["programs"] <= 3 for a in model)


@pytest.mark.parametrize("traced", [False, True])
def test_dispatch_hook_errors_counted_not_fatal(traced):
    tr = Tracer(enabled=traced)
    calls = []

    def bad_hook(rec):
        calls.append(rec)
        raise RuntimeError("subscriber bug")

    server = _server(tracer=tr, on_dispatch=bad_hook)
    outs = server.serve(_reqs(4))   # a hook bug must never fail serving
    assert len(outs) == 4 and all(o is not None for o in outs)
    s = server.stats()
    assert s["requests"] == 4
    assert s["dispatch_hook_errors"] == len(calls) >= 1


def test_stats_windowing_replaces_manual_arithmetic():
    server = _server()
    server.serve(_reqs(5))
    snap = server.snapshot()
    server.serve(_reqs(3, seed=50))
    win = server.stats(since=snap)
    assert win["requests"] == 3, "windowed to traffic after the snapshot"
    assert win["plan_misses"] == 0 and win["registry"]["misses"] == 0
    life = server.stats()
    assert life["requests"] == 8
    assert life["occupancy"] == pytest.approx(
        life["occupied_lanes"] / life["bucket_lanes"])
    # queue-wait/dispatch histograms fed the per-instance registry
    snap_all = server.snapshot()
    assert snap_all["repro.serve.queue_wait_s"]["count"] == 8
    assert snap_all["repro.serve.occupancy"]["bounds"] == \
        list(DEFAULT_RATIO_BUCKETS)
    server.reset_stats()
    z = server.stats()
    assert z["requests"] == 0 and z["registry"]["hits"] == 0
    assert server.snapshot()["repro.serve.queue_wait_s"]["count"] == 0


def test_disabled_tracing_serving_path_allocates_no_spans(monkeypatch):
    """Overhead guard: with the tracer disabled and no profiler session the
    serving paths (per-layer and whole-model, polls included) must not
    construct a single span handle — every span site costs one check."""
    allocs = []
    real = trace_mod._SpanHandle

    class Counting(real):
        def __init__(self, *a, **kw):
            allocs.append(1)
            super().__init__(*a, **kw)

    monkeypatch.setattr(trace_mod, "_SpanHandle", Counting)
    set_default_tracer(Tracer(enabled=False))
    server = _server()
    baseline = len(allocs)   # prewarm may trace nothing either, but be exact
    server.serve(_reqs(6))
    records = []
    sched = _sched_net(None, records)
    sched.session("net").serve([r.x for r in _reqs(3, seed=30)])
    assert len(allocs) == baseline == 0
    assert server.stats()["requests"] == 6
    assert sum(r.requests for r in records) == 3
    # cheap counters/histograms still work without tracing; three requests
    # under an occupancy target of four wait out the gather in polls
    assert server.snapshot()["repro.serve.enqueue_s"]["count"] >= 1
    assert sched.snapshot()["repro.serve.poll_s"]["count"] >= 1


def test_module_level_instrumentation_records_to_default_metrics():
    from repro.plan import make_plan
    make_plan(TINY)
    m = default_metrics()
    assert m.value("repro.plan.builds") >= 1.0
    assert m.value("repro.plan.resolutions") >= 1.0


def test_tune_drift_feed_via_autotune():
    from repro.tune.autotune import autotune_scene
    from repro.tune.cache import ScheduleCache
    cache = ScheduleCache()   # conftest points REPRO_TUNE_CACHE at tmp
    tuned = autotune_scene(TINY, cache=cache,
                           measure_fn=lambda scene, choice: 100.0)
    assert tuned.measured_us == 100.0
    # the winner's (predicted, measured) pair streamed into the monitor
    mon = default_monitor()
    assert sum(s.n for s in mon.stats().values()) == 1
    assert default_metrics().value("repro.tune.scenes_tuned") == 1.0


# -- profiler-trace report ---------------------------------------------------
Plane = collections.namedtuple("Plane", "name lines")
Line = collections.namedtuple("Line", "name events")
Event = collections.namedtuple("Event", "name start_ns duration_ns")


def _ev(name, s, e):
    return Event(name, s, e - s)


def test_obsreport_splits_idle_by_innermost_program_span():
    """Idle time goes to the loop thread's innermost repro.* span, then to
    any thread's, then to the shortest runtime event, then to "no host
    event"; busy plus idle is the window."""
    device = Plane("/device:TPU:0", [Line("XLA Ops", [
        _ev("%a = f32[1] add(f32[1] %x)", 100, 200),
        _ev("%b = f32[1] add(f32[1] %x)", 600, 700)])])
    loop = Line("python", [
        _ev("repro.serve.step", 150, 400),
        _ev("repro.serve.glue", 220, 260),
        _ev("repro.serve.poll", 400, 480)])
    other = Line("python", [
        _ev("repro.serve.submit", 250, 300),      # under loop's step: loses
        _ev("repro.serve.submit", 480, 520)])     # nothing on loop: wins
    runtime = Line("python", [
        _ev("DevicePut", 500, 560), _ev("ExecuteHelper", 400, 590)])
    host = Plane("/host:CPU", [Line("main", [
        _ev("bench.window", 50, 800)]), other, loop, runtime])
    r = _load_script("obsreport").xplane_report([device, host])
    assert r["window_s"] == pytest.approx(750e-9)
    assert r["busy_s"] == pytest.approx(200e-9)
    assert r["idle_s"] + r["busy_s"] == pytest.approx(r["window_s"])
    assert r["idle_by_span"] == pytest.approx({
        "no host event": (100 - 50 + 800 - 700 + 600 - 590) * 1e-9,
        "repro.serve.glue": 40e-9,
        "repro.serve.step": (400 - 200 - 40) * 1e-9,
        "repro.serve.poll": 80e-9,
        "repro.serve.submit": 40e-9,
        "DevicePut": 40e-9,
        "ExecuteHelper": 30e-9})
    assert r["unattributed_s"] == pytest.approx((160 + 70) * 1e-9)
    assert r["loop_thread"] == "python"
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["idle_s"])


def test_obsreport_on_recorded_chip_trace(capsys):
    """On a trace recorded on a TPU v5e (VGG passes, before the program
    had spans): busy is what the benchmark's reduction reads, busy plus
    idle is the window, and no idle time is under a program span."""
    obsreport = _load_script("obsreport")    # puts the repo root on the path
    from bench import trace_reduce
    path = os.path.join(ROOT, "bench", "tests", "data",
                        "vgg16_fprop_v5e.xplane.pb")
    r = obsreport.xplane_file_report(path)
    red = trace_reduce.reduce_file(path)
    assert r["busy_s"] == red["busy_s"]
    assert r["window_s"] == pytest.approx(red["window_s"])
    assert r["busy_s"] + r["idle_s"] == pytest.approx(r["window_s"])
    assert r["idle_s"] > 0 and r["loop_thread"] is None
    assert r["unattributed_s"] == pytest.approx(r["idle_s"])
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["idle_s"])
    assert not any(n.startswith("repro.") for n in r["idle_by_span"])
    # the directory form finds the same file; the CLI prints the split
    assert obsreport.main([os.path.dirname(path), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["busy_s"] == r["busy_s"]
