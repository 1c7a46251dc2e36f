"""repro.obs — unified observability: metrics, span tracing, drift.

The measurement substrate of the tune -> plan -> serve stack:

  ``obs.metrics``  thread-safe ``MetricRegistry`` (counters, gauges,
                   fixed-bucket histograms) with snapshot/delta/reset
                   semantics under the ``repro.<subsystem>.<name>`` scheme;
  ``obs.trace``    span tracing (context manager + decorator, per-thread
                   stacks, explicit ``enabled`` gate, subscribable span
                   stream) with Chrome/Perfetto trace-event JSON export;
  ``obs.drift``    cost-model drift monitor — per-scene-class EWMAs over
                   streamed (predicted, measured) pairs, flagging classes
                   whose error says the calibration artifact is stale.

Instrumented call sites live in ``plan/build.py``, ``plan/registry.py``,
``tune/measure.py``/``autotune.py``/``cache.py``, and ``serve/conv.py``/
``serve/sched.py``; ``scripts/obsreport.py`` renders snapshots, exported
traces and profiler traces post-hoc.
"""
from repro.obs.drift import (DriftMonitor, DriftStat, default_monitor,
                             scene_class, set_default_monitor)
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricRegistry,
                               default_metrics, histogram_percentile,
                               set_default_metrics, snapshot_delta,
                               snapshot_value, summarize_histogram)
from repro.obs.trace import (Tracer, default_tracer, profiler_recording,
                             set_default_tracer)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricRegistry", "default_metrics",
    "set_default_metrics", "snapshot_delta", "snapshot_value",
    "histogram_percentile", "summarize_histogram",
    "Tracer", "default_tracer", "profiler_recording", "set_default_tracer",
    "DriftMonitor", "DriftStat", "default_monitor", "set_default_monitor",
    "scene_class",
]
