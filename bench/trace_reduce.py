"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to what the per-layer
metrics read: device busy and idle time, conv-operation time, non-conv
time, the operations that took most time, and the longest idle gaps named
by what the host was doing.

The window is the host span ``bench.window`` that the loops put around the
measured window (``loops.traced``); without it, the span of the device
operations.  Device time is read from each ``/device:TPU:<n>`` plane's
``XLA Ops`` line, clipped to the window, and averaged over the chips used.

Events are named by their HLO text.  A conv operation is every Mosaic
kernel (the MG3M Pallas kernels lower to a ``custom-call`` with target
``tpu_custom_call``) and every XLA convolution, whatever implements the
conv: see ``is_conv``.  Operations are listed by ``%name = <shape>``.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
TOP = 10


_OPCODE = re.compile(r"\s([a-z][\w-]*)\(")


def opcode(text: str) -> str:
    """The HLO opcode of a trace event named by its HLO text
    (``%name = <shape> <opcode>(<operands>), <attributes>``)."""
    body = text.split(" = ", 1)[-1]
    m = _OPCODE.search(" " + body)
    return m.group(1) if m else ""


def short_name(text: str) -> str:
    """``%name = <shape>`` of an HLO text, without layouts."""
    head = text.split(" = ", 1)
    if len(head) < 2:
        return text[:80]
    return f"{head[0]} = {head[1].split('{', 1)[0].split(' ', 1)[0]}"


def is_conv(text: str) -> bool:
    """A Mosaic kernel (the MG3M Pallas kernels lower to a ``custom-call``
    whose target is ``tpu_custom_call``) or an XLA convolution, plain or
    fused, whatever implements the conv."""
    op = opcode(text)
    if op == "custom-call":
        return 'custom_call_target="tpu_custom_call"' in text
    return op == "convolution" or (op == "fusion" and "convolution" in text)


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _host_events(planes):
    """(start, end, name) of every host event, for naming idle gaps."""
    out = []
    for plane in planes:
        if not plane.name.startswith(HOST_PLANE):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                            ev.name))
    return out


def _window(host_events, device_ops) -> Optional[Tuple[int, int]]:
    spans = [(s, e) for s, e, n in host_events if n == WINDOW_SPAN]
    if spans:
        return min(s for s, _ in spans), max(e for _, e in spans)
    if device_ops:
        return (min(s for s, _, _, _ in device_ops),
                max(e for _, e, _, _ in device_ops))
    return None


def _name_gap(host_events, s: int, e: int) -> str:
    """The shortest host event other than the window that covers the gap's
    midpoint: what the host was doing while the device waited."""
    mid = (s + e) // 2
    best = None
    for hs, he, name in host_events:
        if name == WINDOW_SPAN or not hs <= mid <= he:
            continue
        if best is None or he - hs < best[1] - best[0]:
            best = (hs, he, name)
    return best[2] if best else "no host event"


def reduce_planes(planes, chips: int = 1) -> Optional[dict]:
    """The reduction of one trace's planes; None when the trace holds no
    device operation to read."""
    planes = list(planes)
    devices = sorted((p for p in planes if p.name.startswith(DEVICE_PREFIX)),
                     key=lambda p: int(p.name[len(DEVICE_PREFIX):]))[:chips]
    per_dev = []
    for plane in devices:
        ops = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                ops.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                            short_name(ev.name), is_conv(ev.name)))
        per_dev.append(ops)
    if not any(per_dev):
        return None
    host = _host_events(planes)
    win = _window(host, [o for ops in per_dev for o in ops])
    w0, w1 = win
    busy = conv = nonconv = 0.0
    by_name: Dict[str, float] = {}
    gaps = []
    for k, ops in enumerate(per_dev):
        clipped = [(max(s, w0), min(e, w1), n, c) for s, e, n, c in ops
                   if e > w0 and s < w1]
        for s, e, n, c in clipped:
            d = (e - s) * 1e-9
            if c:
                conv += d
            else:
                nonconv += d
            by_name[n] = by_name.get(n, 0.0) + d
        merged = _union([(s, e) for s, e, _, _ in clipped])
        busy += sum(e - s for s, e in merged) * 1e-9
        if k == 0:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            for s, e in zip(edges[::2], edges[1::2]):
                if e > s:
                    gaps.append((s, e))
    n = len(per_dev)
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy / n,
        "conv_s": conv / n,
        "nonconv_s": nonconv / n,
        "chips_traced": n,
        "device_ops": [[name, sec / n] for name, sec in
                       sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[_name_gap(host, s, e), (e - s) * 1e-9]
                      for s, e in gaps[:TOP]],
    }


def trace_files(trace_dir: str) -> List[str]:
    return sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))


def reduce_file(path: str, chips: int = 1) -> Optional[dict]:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, chips)


def reduce_dir(trace_dir: str, chips: int = 1) -> Optional[dict]:
    """Reduce the newest trace under ``trace_dir`` (None if there is none
    or it holds no device operation)."""
    files = trace_files(trace_dir)
    if not files:
        return None
    return reduce_file(max(files, key=os.path.getmtime), chips)
