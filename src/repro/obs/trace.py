"""Structured span tracing on the profiler's clock, with Chrome/Perfetto
trace-event export.

A span is opened with ``with tracer.span("repro.x.y", k=v):`` (or the
``@tracer.traced()`` decorator).  What it records depends on two switches:

* a ``jax.profiler`` session is recording (``start_trace`` or the profiler
  server): the span opens a ``jax.profiler.TraceAnnotation`` of the same
  name and args on the calling thread, so it lands on the trace's host
  plane beside the runtime's own events and can name the device's idle
  gaps.  This holds whether or not the tracer is ``enabled``;
* the tracer is ``enabled``: the span is also buffered as a complete
  trace event ("ph": "X") for ``tracer.export(path)``, whose JSON loads in
  ``chrome://tracing`` and https://ui.perfetto.dev.  Its ``ts`` is the
  host's wall clock in µs since the Unix epoch (``time.time_ns``), the
  clock the profiler stamps host events with: an exported span starts
  where the same span sits in the ``.xplane.pb`` (at
  ``profile_start_time`` plus the event's offset), to within a few µs.

Neither switch touches the device: a span never waits on a result.

Overhead contract (the serving hot path depends on it): with neither
switch on, ``span()`` costs one ``TraceMe.is_enabled()`` call and one
attribute check, and returns a shared no-op handle: no span object, no
annotation (the call's own ``**args`` dict is the one allocation).  Span
sites whose args cost something set them on the live handle only
(``if sp: sp.set(...)``; the no-op handle is falsy); lint rule
``hot-path-alloc`` holds the serving and plan layers to that.
"""
from __future__ import annotations

import collections
import functools
import json
import os
import tempfile
import threading
import time
from typing import Callable, Deque, Dict, List, Optional

from jax.profiler import TraceAnnotation

__all__ = ["Tracer", "default_tracer", "profiler_recording",
           "set_default_tracer"]

# True while a profiler session records host events (a static method of
# the annotation's ``TraceMe`` base, well under a µs a call).
profiler_recording: Callable[[], bool] = TraceAnnotation.is_enabled


class _NoopSpan:
    """Shared do-nothing handle returned while nothing records."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def __bool__(self) -> bool:
        return False

    def set(self, **kwargs) -> "_NoopSpan":
        return self


_NOOP = _NoopSpan()


class _Annotation(TraceAnnotation):
    """Live span while only the profiler records: the profiler's own
    annotation, with the handle's ``set``, and nothing buffered."""

    def set(self, **kwargs) -> "_Annotation":
        """Attach/overwrite args on the profiler's event."""
        self.set_metadata(**kwargs)
        return self


class _SpanHandle:
    """Live span of an enabled tracer: a buffered event, and a profiler
    annotation too when ``profiled``.  Only ever constructed while the
    tracer is enabled (tests assert the idle path allocates none)."""

    __slots__ = ("_tracer", "name", "args", "_t0", "_ann", "_buffered")

    def __init__(self, tracer: "Tracer", name: str, args: Dict,
                 profiled: bool):
        self._tracer = tracer
        self.name = name
        self.args = args
        self._t0 = 0
        self._ann = TraceAnnotation(name, **args) if profiled else None
        self._buffered = tracer.enabled

    def set(self, **kwargs) -> "_SpanHandle":
        """Attach/overwrite args on the live span (in the profiler's event
        and in the exported one)."""
        self.args.update(kwargs)
        if self._ann is not None:
            self._ann.set_metadata(**kwargs)
        return self

    def __enter__(self) -> "_SpanHandle":
        if self._buffered:
            stack = self._tracer._stack()
            if stack:
                self.args.setdefault("parent", stack[-1].name)
            stack.append(self)
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.time_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.time_ns()
        if self._ann is not None:
            if exc_type is not None:
                self._ann.set_metadata(error=exc_type.__name__)
            self._ann.__exit__(exc_type, exc, tb)
        if self._buffered:
            stack = self._tracer._stack()
            if stack and stack[-1] is self:
                stack.pop()
            if exc_type is not None:
                self.args.setdefault("error", exc_type.__name__)
            self._tracer._finish(self, self._t0, t1)
        return False


class Tracer:
    """Span recorder with an explicit ``enabled`` gate for the buffer (the
    profiler's annotations follow the profiler session, see the module
    docstring).

    ``max_events`` bounds memory as a ring buffer: the newest spans win and
    ``dropped_events`` counts what fell off — a long soak with tracing left
    on degrades to a rolling window, never to an OOM.
    """

    def __init__(self, *, enabled: bool = False, max_events: int = 200_000):
        self.enabled = enabled
        self.max_events = max_events
        self._events: Deque[Dict] = collections.deque(maxlen=max_events)
        self.dropped_events = 0
        self._lock = threading.Lock()
        self._tls = threading.local()

    # -- span API ------------------------------------------------------------
    def span(self, name: str, **args):
        """Context manager for one span.  With no profiler session and the
        tracer disabled it returns the shared no-op handle: one check, no
        span object."""
        profiled = profiler_recording()
        if not (profiled or self.enabled):
            return _NOOP
        if not self.enabled:
            return _Annotation(name, **args)
        return _SpanHandle(self, name, args, profiled)

    def traced(self, name: Optional[str] = None) -> Callable:
        """Decorator form: spans every call of the wrapped function."""
        def deco(fn):
            span_name = name or f"{fn.__module__}.{fn.__qualname__}"

            @functools.wraps(fn)
            def wrapper(*a, **kw):
                with self.span(span_name):
                    return fn(*a, **kw)
            return wrapper
        return deco

    def current(self) -> Optional[str]:
        """Name of this thread's innermost open buffered span, if any."""
        stack = self._stack()
        return stack[-1].name if stack else None

    def _stack(self) -> List["_SpanHandle"]:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _finish(self, handle: "_SpanHandle", t0_ns: int, t1_ns: int) -> None:
        event = {
            "ph": "X", "cat": "repro", "name": handle.name,
            "ts": t0_ns / 1e3,                  # trace-event µs, wall clock
            "dur": (t1_ns - t0_ns) / 1e3,
            "pid": os.getpid(), "tid": threading.get_ident(),
            "args": handle.args,
        }
        with self._lock:
            if len(self._events) == self.max_events:
                self.dropped_events += 1
            self._events.append(event)

    # -- buffer --------------------------------------------------------------
    def events(self) -> List[Dict]:
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self.dropped_events = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def export(self, path: str) -> str:
        """Write the buffered spans as Chrome trace-event JSON (atomic
        tmp+rename).  Open in chrome://tracing or https://ui.perfetto.dev."""
        p = os.path.abspath(os.path.expanduser(path))
        doc = {"traceEvents": self.events(), "displayTimeUnit": "ms",
               "otherData": {"producer": "repro.obs.trace",
                             "dropped_events": self.dropped_events}}
        os.makedirs(os.path.dirname(p) or ".", exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(p) or ".",
                                   suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(doc, f)
            os.replace(tmp, p)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        return p


# -- process-global default tracer (disabled until someone enables it) -------
_default: Optional[Tracer] = None
_default_lock = threading.Lock()


def default_tracer() -> Tracer:
    global _default
    if _default is None:
        with _default_lock:
            if _default is None:
                _default = Tracer()
    return _default


def set_default_tracer(tracer: Optional[Tracer]) -> None:
    """Install (or with None, reset) the process-global tracer — tests."""
    global _default
    _default = tracer
