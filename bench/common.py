"""Helpers the loops in ``loops/`` share: seeds, weights and inputs made on
the device, scenes from a configuration's layers, the compile counter, the
profiler window, the collector log, the comparison's error and the
device's memory peak."""
from __future__ import annotations

import contextlib
import gc
import math
import time

import jax
import jax.numpy as jnp

from bench.trace_reduce import WINDOW_SPAN

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Counts XLA compiles and persistent-cache loads while the ``with``
    block runs (copied from the repository's chip smoke test)."""

    def __init__(self):
        self.n = 0

    def _on_duration(self, event, duration_secs, **kwargs):
        if event == BACKEND_COMPILE:
            self.n += 1

    def _on_event(self, event, **kwargs):
        if event == CACHE_HIT:
            self.n += 1

    def __enter__(self) -> "CompileCounter":
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> bool:
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
        return False


class GcLog:
    """Every collection of Python's garbage collector while the ``with``
    block runs: its generation and how long it held the interpreter, so
    that a stall inside the window can be laid against the collections."""

    def __init__(self):
        self.pauses = []            # (generation, start, seconds)
        self._t = None

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"], self._t,
                                time.perf_counter() - self._t))
            self._t = None

    def __enter__(self) -> "GcLog":
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc) -> bool:
        gc.callbacks.remove(self._cb)
        return False

    def summary(self) -> dict:
        s = [p[2] for p in self.pauses]
        return {"gc_collections": len(s), "gc_pause_sum_s": sum(s),
                "gc_pause_max_s": max(s, default=0.0),
                "gc_gen2_collections": sum(p[0] == 2 for p in self.pauses)}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number: ``PRNGKey`` keeps only the low 32
    bits, so the rest is folded in."""
    seed %= 2 ** 64
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def scene_of(layer, batch: int, dtype: str):
    from repro.core.scene import ConvScene
    return ConvScene(B=batch, IC=layer["IC"], OC=layer["OC"],
                     inH=layer["in_hw"], inW=layer["in_hw"],
                     fltH=layer["flt"], fltW=layer["flt"],
                     padH=layer["pad"], padW=layer["pad"],
                     stdH=layer["stride"], stdW=layer["stride"], dtype=dtype)


def he_weights(key, layers, dtype):
    """He-normal filters of every layer, made on the device in one call."""
    shapes = tuple((l["flt"], l["flt"], l["IC"], l["OC"]) for l in layers)

    @jax.jit
    def make(k):
        ks = jax.random.split(k, len(shapes))
        return tuple(
            (jax.random.normal(kk, s, jnp.float32)
             * math.sqrt(2.0 / (s[0] * s[1] * s[2]))).astype(dtype)
            for kk, s in zip(ks, shapes))
    return list(make(key))


def normal_arrays(key, shapes, dtype):
    @jax.jit
    def make(k):
        ks = jax.random.split(k, len(shapes))
        return tuple(jax.random.normal(kk, s, jnp.float32).astype(dtype)
                     for kk, s in zip(ks, shapes))
    return list(make(key))


@jax.jit
def rel_err(got, want):
    """Largest |got - want| over the largest |want|, in float32."""
    got = got.astype(jnp.float32)
    want = want.astype(jnp.float32)
    return jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))


def memory_peak_bytes(chips: int):
    peaks = []
    for d in jax.local_devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


@contextlib.contextmanager
def traced(trace_dir):
    """The profiler around the measured window (when ``trace_dir`` is set),
    with the window marked by a host span the trace reduction finds.
    Python's function tracer stays off: it slows a host-bound loop several
    times over, so that a traced serving window would no longer carry the
    cell's load; the runtime's host events still name the idle gaps."""
    if trace_dir is None:
        yield
        return
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield
    finally:
        jax.profiler.stop_trace()


def check(checks, name, value, limit):
    checks[name] = (float(value), float(limit))
