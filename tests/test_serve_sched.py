"""Latency-aware scheduling over the conv serving engine
(``repro.serve.sched``): deadline-flushed partial buckets stay bitwise
identical to full-rung and per-request dispatch, EDF keeps the queue
urgency-ordered and sheds the least urgent entry, the bounded queue rejects
with a typed ``Overloaded``, strict steady state stays zero-resolution
through deadline flushes and model pipelines, and ``ModelSession`` whole-
model outputs match layer-by-layer serving across paper CNNs."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.plan.build as build_mod
from repro.models.cnn import cnn_chain_scenes, cnn_layer_scenes
from repro.obs.trace import Tracer
from repro.serve import (ConvRequest, ConvScheduler, ModelRequest,
                         Overloaded, SchedConfig, scheduler_from_scenes,
                         server_from_scenes)

CAPS = dict(max_hw=8, max_ch=8, layers_per_net=2)


def _x(scene, b, seed):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (scene.inH, scene.inW, scene.IC, b), jnp.float32)


def _sched(layers, *, max_batch=8, config=None, **kw):
    # slack=0 keeps the full pow2 ladder on capped scenes, so every test
    # that wants gathering must set an explicit occupancy_target (the
    # unpruned sweet spot is rung 1)
    return scheduler_from_scenes(layers, max_batch=max_batch,
                                 ladder_slack=0.0, strict=True,
                                 config=config, **kw)


# -- config validation -------------------------------------------------------
def test_sched_config_validation():
    with pytest.raises(ValueError, match="shed_policy"):
        SchedConfig(shed_policy="drop-oldest")
    with pytest.raises(ValueError, match="max_queue"):
        SchedConfig(max_queue=-1)
    with pytest.raises(ValueError, match="max_gather_s"):
        SchedConfig(max_gather_s=0.0)
    with pytest.raises(ValueError, match="max_gather_s"):
        SchedConfig(max_gather_s=float("inf"))
    with pytest.raises(ValueError, match="flush_margin_s"):
        SchedConfig(flush_margin_s=-0.1)
    with pytest.raises(ValueError, match="poll_s"):
        SchedConfig(poll_s=0.0)
    with pytest.raises(ValueError, match="mesh"):
        ConvScheduler(mesh=object())


# -- deadline flush ----------------------------------------------------------
def test_deadline_flush_partial_bucket_bitwise_parity():
    """Three B=1 requests against an occupancy target of 8: without a
    deadline nothing flushes; with one, the group dispatches at the
    cheapest warmed sub-rung bucket (4) and every lane is bitwise what the
    full-rung and per-request B=1 paths produce."""
    layers = cnn_layer_scenes(("alexnet",), **CAPS)
    name = next(iter(layers))
    records = []
    sched = _sched(layers,
                   config=SchedConfig(occupancy_target=8, max_gather_s=5.0),
                   on_dispatch=records.append)
    sched.prewarm()

    xs = [_x(layers[name], 1, seed) for seed in range(3)]
    reqs = [sched.submit(ConvRequest(rid=i, layer=name, x=x,
                                     deadline_s=0.015))
            for i, x in enumerate(xs)]
    assert sched.step() == 0, "deadline far away: keep gathering"
    sched.drain()
    assert all(r.done for r in reqs)

    assert len(records) == 1
    rec = records[0]
    assert rec.bucket == 4 and rec.occupied == 3 and rec.requests == 3
    s = sched.stats()
    assert s["deadline_flushes"] == 1 and s["occupancy_flushes"] == 0
    assert s["plan_misses"] == 0 and s["plan_builds"] == 0

    # per-request B=1 parity (bitwise: padded lanes are independent columns)
    fam = sched._layers[name]
    for r, x in zip(reqs, xs):
        want = sched.registry.get_or_build(
            fam.base.with_batch(1)).execute(x, fam.flt)
        assert np.array_equal(np.asarray(r.out), np.asarray(want))

    # full-rung parity: the same inputs padded out to a full occupancy
    # flush produce the same lanes
    full = [sched.submit(ConvRequest(rid=10 + i, layer=name, x=x))
            for i, x in enumerate(xs)]
    full += [sched.submit(ConvRequest(rid=20 + i, layer=name,
                                      x=_x(layers[name], 1, 50 + i)))
             for i in range(5)]
    assert sched.step() == 8, "8 lanes == occupancy target: flush now"
    assert records[-1].bucket == 8
    assert sched.stats()["occupancy_flushes"] == 1
    for r_part, r_full in zip(reqs, full[:3]):
        assert np.array_equal(np.asarray(r_part.out), np.asarray(r_full.out))


def test_gather_timeout_bounds_deadline_less_requests():
    layers = cnn_layer_scenes(("alexnet",), **CAPS)
    name = next(iter(layers))
    sched = _sched(layers, config=SchedConfig(occupancy_target=8,
                                              max_gather_s=0.01))
    sched.prewarm()
    r = sched.submit(ConvRequest(rid=0, layer=name, x=_x(layers[name], 1, 0)))
    assert sched.drain() == 1 and r.done
    s = sched.stats()
    assert s["gather_timeout_flushes"] == 1 and s["deadline_flushes"] == 0


def test_deadline_miss_accounting_blocks_on_result():
    """A deadline that cannot be met is recorded as a miss — and because
    accounting blocks on the dispatched result, the miss means "tensor not
    ready in time", not "not enqueued in time"."""
    layers = cnn_layer_scenes(("alexnet",), **CAPS)
    name = next(iter(layers))
    sched = _sched(layers, config=SchedConfig(occupancy_target=8))
    sched.prewarm()
    sched.submit(ConvRequest(rid=0, layer=name, x=_x(layers[name], 1, 0),
                             deadline_s=1e-4))
    sched.drain()
    s = sched.stats()
    assert s["deadline_requests"] == 1 and s["deadline_misses"] == 1
    assert s["deadline_miss_rate"] == 1.0


def test_submit_rejects_bad_deadlines():
    layers = cnn_layer_scenes(("alexnet",), **CAPS)
    name = next(iter(layers))
    sched = _sched(layers)
    with pytest.raises(ValueError, match="deadline_s"):
        sched.submit(ConvRequest(rid=0, layer=name, x=_x(layers[name], 1, 0),
                                 deadline_s=0.0))


# -- admission control -------------------------------------------------------
def test_bounded_queue_reject_newest():
    layers = cnn_layer_scenes(("alexnet",), **CAPS)
    name = next(iter(layers))
    sched = _sched(layers, config=SchedConfig(max_queue=2, occupancy_target=8,
                                              max_gather_s=0.01))
    sched.prewarm()
    kept = [sched.submit(ConvRequest(rid=i, layer=name,
                                     x=_x(layers[name], 1, i)))
            for i in range(2)]
    with pytest.raises(Overloaded, match="queue full"):
        sched.submit(ConvRequest(rid=2, layer=name, x=_x(layers[name], 1, 2)))
    s = sched.stats()
    assert s["shed"] == 1 and s["queued"] == 2
    # the accepted prefix still completes — targeted loss, not collapse
    sched.drain()
    assert all(r.done and r.error is None for r in kept)


def test_edf_sheds_least_urgent_and_orders_queue():
    layers = cnn_layer_scenes(("alexnet",), **CAPS)
    name = next(iter(layers))
    sched = _sched(layers, config=SchedConfig(
        max_queue=2, shed_policy="edf", occupancy_target=8,
        max_gather_s=0.05))
    sched.prewarm()
    loose = sched.submit(ConvRequest(rid=0, layer=name,
                                     x=_x(layers[name], 1, 0)))
    mid = sched.submit(ConvRequest(rid=1, layer=name,
                                   x=_x(layers[name], 1, 1), deadline_s=5.0))
    # EDF insertion: deadline-less last
    assert list(sched._queue) == [mid, loose]
    # overflow sheds the *least* urgent (the deadline-less request), not
    # the arrival; its waiter unblocks with the typed error
    tight = sched.submit(ConvRequest(rid=2, layer=name,
                                     x=_x(layers[name], 1, 2),
                                     deadline_s=1.0))
    assert list(sched._queue) == [tight, mid]
    assert loose.done and isinstance(loose.error, Overloaded)
    assert sched.wait([loose], raise_on_error=False) == [None]
    with pytest.raises(RuntimeError, match="failed"):
        sched.wait([loose])
    assert sched.stats()["shed"] == 1
    sched.drain()
    assert tight.done and mid.done and tight.error is None


# -- strict steady state -----------------------------------------------------
def test_strict_zero_resolution_steady_state(monkeypatch):
    """After prewarm, a mixed trace — deadline flushes at sub-rung buckets,
    occupancy flushes, whole-model sessions — must never resolve a
    schedule or build a plan (the PR 5 contract survives the scheduler)."""
    layers = cnn_layer_scenes(("alexnet",), **CAPS)
    chain = cnn_chain_scenes("resnet", **CAPS)
    sched = _sched(layers, config=SchedConfig(occupancy_target=8,
                                              max_gather_s=0.01))
    sched.register_net("resnet", chain, seed=3)
    sched.prewarm(compile=True)
    # one compiled chain per flush rung, all of them built by the prewarm
    rungs = sched.flush_ladders()[next(iter(chain))]
    builds = sched.snapshot()["repro.serve.chain_builds"]["value"]
    assert builds == len(rungs)

    def forbidden(*a, **kw):
        raise AssertionError("post-warm schedule resolution")
    monkeypatch.setattr(build_mod, "select_schedule", forbidden)

    name = next(iter(layers))
    sess = sched.session("resnet")
    reqs = [sched.submit(ConvRequest(rid=i, layer=name,
                                     x=_x(layers[name], 1, i),
                                     deadline_s=0.005))
            for i in range(3)]
    sc0 = chain[next(iter(chain))]
    mreqs = [sess.submit(_x(sc0, 1, 100 + i)[..., 0], deadline_s=0.005)
             for i in range(2)]
    sched.drain()
    assert all(r.done and r.error is None for r in reqs + mreqs)
    s = sched.stats()
    assert s["plan_misses"] == 0 and s["plan_builds"] == 0
    assert s["registry"]["misses"] == 0
    assert s["deadline_flushes"] >= 1
    # a second prewarm and a burst at every group size compile no chain
    sched.prewarm(compile=True)
    sess.serve([_x(sc0, 1, 200 + i) for i in range(8)])
    sess.serve([_x(sc0, 1, 300 + i)[..., 0] for i in range(5)])
    assert sched.snapshot()["repro.serve.chain_builds"]["value"] == builds


def test_warmed_buckets_probe():
    """The registry answers "which buckets can a deadline flush execute"
    without traffic side effects: the full flush ladder after prewarm."""
    layers = cnn_layer_scenes(("alexnet",), max_hw=8, max_ch=8,
                              layers_per_net=1)
    name = next(iter(layers))
    sched = _sched(layers, max_batch=8)
    base = sched._layers[name].base
    assert sched.registry.warmed_buckets(base) == ()
    sched.prewarm()
    snap = sched.registry.stats()
    assert sched.registry.warmed_buckets(base) == (1, 2, 4, 8)
    assert sched.flush_ladders()[name] == (1, 2, 4, 8)
    after = sched.registry.stats()
    assert (after["hits"], after["misses"]) == (snap["hits"], snap["misses"])


# -- whole-model sessions ----------------------------------------------------
def _layer_by_layer(chain, flts, xs, activation=None):
    """Each input served alone through a plain ``ConvServer``, one layer
    per request, the activation applied between layers."""
    server = server_from_scenes(chain, flts, max_batch=8, ladder_slack=0.0,
                                strict=True)
    server.prewarm()
    outs = []
    for x in xs:
        cur = x
        for i, lname in enumerate(chain):
            r = ConvRequest(rid=i, layer=lname, x=cur)
            server.serve([r])
            cur = r.out if activation is None else activation(r.out)
        outs.append(cur)
    return outs


@pytest.mark.parametrize("net", ["alexnet", "resnet"])
def test_model_session_parity_vs_layer_by_layer(net):
    """A ``ModelSession`` burst through a registered chain is bitwise (f32)
    what a plain ``ConvServer`` produces serving the same images layer by
    layer — pipelining the coalesced activation in one compiled program is
    a layout move, never a numeric one — at every group size 1-8, for 3-D
    (squeezed) and 4-D requests, and for a b > 1 request among single
    images."""
    chain = cnn_chain_scenes(net, **CAPS)
    sched = ConvScheduler(max_batch=8, ladder_slack=0.0, strict=True,
                          config=SchedConfig(occupancy_target=8,
                                             max_gather_s=0.02))
    sched.register_net(net, chain, seed=9)
    sched.prewarm()
    flts = {ln: sched._layers[ln].flt for ln in chain}

    sc0 = chain[next(iter(chain))]
    sess = sched.session(net)
    records = []
    sched.on_dispatch = records.append
    groups = [[_x(sc0, 1, 40 + 10 * g + i) for i in range(g)]
              for g in range(1, 9)]
    # odd positions go in 3-D and must come back 3-D
    groups = [[x[..., 0] if i % 2 else x for i, x in enumerate(xs)]
              for xs in groups]
    groups.append([_x(sc0, 3, 7)] + [_x(sc0, 1, 8 + i)[..., 0]
                                     for i in range(5)])
    for xs in groups:
        outs = sess.serve(xs)
        for x, out, want in zip(xs, outs, _layer_by_layer(chain, flts, xs)):
            assert out.ndim == x.ndim
            assert np.array_equal(np.asarray(out), np.asarray(want))
    assert [r.occupied for r in records] == [1, 2, 3, 4, 5, 6, 7, 8, 8]
    s = sched.stats()
    assert s["dispatches"] == len(groups) and s["plan_misses"] == 0


def test_model_chain_relu_parity_vs_layer_by_layer():
    """With ReLU between layers (the activation the All-CNN-C cell
    serves), the compiled chain is still bitwise layer-by-layer serving
    with an eager ReLU after every layer: ReLU is exact."""
    chain = cnn_chain_scenes("vgg", **CAPS)
    sched = ConvScheduler(max_batch=8, ladder_slack=0.0, strict=True,
                          config=SchedConfig(occupancy_target=8,
                                             max_gather_s=0.02))
    sched.register_net("vgg", chain, seed=5, activation=jax.nn.relu)
    sched.prewarm(compile=True)
    flts = {ln: sched._layers[ln].flt for ln in chain}
    sc0 = chain[next(iter(chain))]
    xs = [_x(sc0, 1, 60 + i)[..., 0] for i in range(5)]
    outs = sched.session("vgg").serve(xs)
    wants = _layer_by_layer(chain, flts, xs, activation=jax.nn.relu)
    for out, want in zip(outs, wants):
        assert np.array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_model_dispatch_enqueues_at_most_three_programs(depth):
    """Whatever the layer count or group size, a whole-model group is at
    most three host enqueues — gather, chain program, split — read from
    the ``programs`` arg of its ``repro.serve.model_dispatch`` span; a lone
    full-bucket 4-D request needs neither glue call."""
    chain = cnn_chain_scenes("alexnet", max_hw=8, max_ch=8,
                             layers_per_net=depth)
    tracer = Tracer(enabled=True)
    sched = ConvScheduler(max_batch=8, ladder_slack=0.0, strict=True,
                          tracer=tracer,
                          config=SchedConfig(occupancy_target=8,
                                             max_gather_s=0.01))
    sched.register_net("alexnet", chain)
    sched.prewarm()
    sess = sched.session("alexnet")
    sc0 = chain[next(iter(chain))]
    sess.serve([_x(sc0, 1, i)[..., 0] for i in range(7)])
    sess.serve([_x(sc0, 1, 20)])
    spans = [e["args"] for e in tracer.events()
             if e["name"] == "repro.serve.model_dispatch"]
    assert [(a["requests"], a["layers"], a["programs"]) for a in spans] == [
        (7, len(chain), 3), (1, len(chain), 1)]
    assert not any(e["name"] == "repro.serve.layer_dispatch"
                   for e in tracer.events())


def test_chain_activation_error_fails_group_loop_serves_next():
    """An activation that raises inside the chain program fails every
    waiter of its group with that error, and the background loop goes on
    to serve the next group."""
    chain = cnn_chain_scenes("alexnet", **CAPS)
    calls = []

    def flaky_relu(x):
        calls.append(1)
        if len(calls) == 1:
            raise FloatingPointError("activation blew up")
        return jax.nn.relu(x)

    sched = ConvScheduler(max_batch=8, ladder_slack=0.0, strict=True,
                          config=SchedConfig(occupancy_target=8,
                                             max_gather_s=0.01))
    sched.register_net("alexnet", chain, activation=flaky_relu)
    sched.prewarm()
    sess = sched.session("alexnet")
    sc0 = chain[next(iter(chain))]
    # queued before the loop starts, so the three form one group
    bad = [sess.submit(_x(sc0, 1, i)) for i in range(3)]
    sched.start()
    try:
        sched.wait(bad, raise_on_error=False)
        good = [sess.submit(_x(sc0, 1, 10 + i)) for i in range(3)]
        outs = sched.wait(good)
    finally:
        sched.stop()
    assert all(isinstance(r.error, FloatingPointError) and r.out is None
               for r in bad)
    with pytest.raises(RuntimeError, match="failed in a coalesced"):
        sched.wait(bad[:1])
    assert all(r.error is None for r in good)
    assert all(o is not None for o in outs)
    assert sched.stats()["requests"] == 3


def test_model_session_validation_and_registration():
    chain = cnn_chain_scenes("alexnet", **CAPS)
    sched = ConvScheduler(max_batch=4, ladder_slack=0.0, strict=True)
    sched.register_net("alexnet", chain)
    with pytest.raises(ValueError, match="already registered"):
        sched.register_net("alexnet", chain)
    with pytest.raises(KeyError, match="unknown net"):
        sched.session("vgg")
    assert sched.nets() == {"alexnet": tuple(chain)}
    sess = sched.session("alexnet")
    sched.prewarm()
    sc0 = chain[next(iter(chain))]
    with pytest.raises(ValueError, match="expects a"):
        sess.submit(jnp.zeros((1, 1, 1, 1), jnp.float32))
    with pytest.raises(ValueError, match="exceeds"):
        sess.submit(_x(sc0, 8, 0))
    with pytest.raises(ValueError, match="deadline_s"):
        sess.submit(_x(sc0, 1, 0), deadline_s=-1.0)
    # 3-D submit round-trips squeezed, batched stays batched
    r3 = sess.submit(_x(sc0, 1, 1)[..., 0])
    r4 = sess.submit(_x(sc0, 2, 2))
    sched.drain()
    last = chain[list(chain)[-1]]
    assert r3.out.shape == (last.outH, last.outW, last.OC)
    assert r4.out.shape == (last.outH, last.outW, last.OC, 2)


def test_model_session_background_loop():
    """start()/stop(): clients just submit and wait while the scheduler
    thread flushes on deadlines — continuous batching end to end."""
    chain = cnn_chain_scenes("alexnet", **CAPS)
    sched = ConvScheduler(max_batch=8, ladder_slack=0.0, strict=True,
                          config=SchedConfig(occupancy_target=8,
                                             max_gather_s=0.05))
    sched.register_net("alexnet", chain)
    sched.prewarm()
    sess = sched.session("alexnet")
    sc0 = chain[next(iter(chain))]
    sched.start()
    try:
        with pytest.raises(RuntimeError, match="already running"):
            sched.start()
        reqs = [sess.submit(_x(sc0, 1, i), deadline_s=0.5)
                for i in range(3)]
        outs = sched.wait(reqs)
    finally:
        sched.stop()
    assert all(o is not None for o in outs)
    assert sched.stats()["queued"] == 0
    # a ModelRequest routed through plain submit() still lands correctly
    r = sched.submit(ModelRequest(rid=next(sched._seq), layer="",
                                  x=_x(sc0, 1, 9), net="alexnet"))
    sched.drain()
    assert r.done and r.layer == "@alexnet"


# -- chain scenes ------------------------------------------------------------
def test_cnn_chain_scenes_chain_and_caps():
    for net in ("alexnet", "vgg", "resnet", "yolo"):
        chain = cnn_chain_scenes(net, max_hw=8, max_ch=8)
        items = list(chain.items())
        assert all(n.startswith(f"{net}/L") for n, _ in items)
        for (_, a), (_, b) in zip(items, items[1:]):
            assert (a.outH, a.outW, a.OC) == (b.inH, b.inW, b.IC)
        assert all(sc.inH <= 8 and sc.IC <= 8 and sc.OC <= 8
                   for _, sc in items)
    assert len(cnn_chain_scenes("vgg", max_hw=8, max_ch=8,
                                layers_per_net=2)) == 2
    with pytest.raises(KeyError):
        cnn_chain_scenes("lenet")


# -- observability -----------------------------------------------------------
def test_slo_report_and_layer_trace(tmp_path):
    """The scheduler's counters surface through obsreport's slo section;
    a traced model dispatch is one span per group whose ``layers`` and
    ``programs`` args the trace report groups by net, and each layer's
    named scope is in the compiled chain's op names."""
    import importlib.util
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "scripts",
                        "obsreport.py")
    spec = importlib.util.spec_from_file_location("obsreport", path)
    obsreport = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(obsreport)

    chain = cnn_chain_scenes("alexnet", **CAPS)
    tracer = Tracer()
    tracer.enabled = True
    sched = ConvScheduler(max_batch=8, ladder_slack=0.0, strict=True,
                          tracer=tracer,
                          config=SchedConfig(occupancy_target=8,
                                             max_gather_s=0.01))
    sched.register_net("alexnet", chain)
    sched.prewarm()
    sess = sched.session("alexnet")
    sc0 = chain[next(iter(chain))]
    sess.serve([_x(sc0, 1, i) for i in range(3)], deadline_s=0.01)

    mpath = tmp_path / "metrics.json"
    sched.metrics.dump(str(mpath))
    report = obsreport.metrics_report(json.loads(mpath.read_text()))
    slo = report["slo"]
    assert slo["deadline_requests"] == 3
    assert slo["flushes"]["deadline"] + slo["flushes"]["gather_timeout"] >= 1
    # one enqueue per dispatch; the gather until the deadline flush polls
    assert slo["enqueue"]["count"] == slo["flushes"]["deadline"] + \
        slo["flushes"]["gather_timeout"] + slo["flushes"]["occupancy"]
    assert slo["poll"]["count"] >= 1
    assert "layer_dispatch" not in slo and "dispatch" not in slo

    tpath = tmp_path / "trace.json"
    tracer.export(str(tpath))
    treport = obsreport.trace_report(json.loads(tpath.read_text()))
    # one model_dispatch per group, carrying its layer count and host
    # enqueues; no per-layer host span is left
    spans = treport["spans"]
    assert spans["repro.serve.model_dispatch"]["count"] == \
        slo["enqueue"]["count"]
    assert "repro.serve.layer_dispatch" not in spans
    net_stats = treport["layers"]
    assert set(net_stats) == {"alexnet"}
    assert net_stats["alexnet"]["count"] == slo["enqueue"]["count"]
    assert net_stats["alexnet"]["layers"] == len(chain)
    assert 1 <= net_stats["alexnet"]["programs_max"] <= 3
    # the per-layer split lives in the compiled program's op names
    for (net, bucket), prog in sched._chains.items():
        hlo = prog.run.as_text()
        for lname in chain:
            assert f"repro.serve.{lname}/" in hlo, (bucket, lname)


def test_scheduler_concurrent_submitters():
    """Many threads submitting against one background loop: every request
    completes exactly once and steady state stays zero-miss."""
    layers = cnn_layer_scenes(("alexnet",), **CAPS)
    name = next(iter(layers))
    sched = _sched(layers, config=SchedConfig(occupancy_target=4,
                                              max_gather_s=0.02))
    sched.prewarm()
    done: list = []
    lock = threading.Lock()

    def client(seed):
        r = sched.submit(ConvRequest(rid=seed, layer=name,
                                     x=_x(layers[name], 1, seed),
                                     deadline_s=0.5))
        sched.wait([r])
        with lock:
            done.append(r)
    sched.start()
    try:
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        sched.stop()
    assert len(done) == 12
    assert all(r.done and r.error is None and r.out is not None
               for r in done)
    s = sched.stats()
    assert s["plan_misses"] == 0 and s["requests"] == 12
