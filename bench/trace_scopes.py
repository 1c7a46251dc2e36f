"""Device time of a training step's trace split by what the program named:
the layer graph's ``repro.graph.*`` named scopes and the direction of each
conv kernel.

A device operation in the profiler's trace is named by its HLO text,
``%<instruction> = ...`` (``trace_reduce.py``).  Its scope path is the
``op_name`` metadata of that instruction in the compiled program's own
HLO text (``compiled.as_text()``), for example
``jit(train_step)/transpose(jvp(repro.graph.s2b0))/repro.graph.bn/mul``:
the innermost ``repro.graph.<scope>`` on that path is the op's scope.  A
conv kernel's direction is the plan executor its instruction was inlined
from, which XLA keeps in the instruction's name: ``_exec_fprop``,
``_exec_dgrad`` or ``_exec_wgrad``.
"""
from __future__ import annotations

import os
import re
from typing import Dict, Optional

from bench import trace_reduce

GRAPH_SCOPES = ("bn", "pool", "add")
DIRECTIONS = ("fprop", "dgrad", "wgrad")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_SCOPE = re.compile(r"repro\.graph\.(\w+)")


def instruction(text: str) -> str:
    """The instruction name of an HLO text line or a trace event's name."""
    m = _INSTR.match(text)
    return m.group(1) if m else ""


def op_names(hlo_text: str) -> Dict[str, str]:
    """``{instruction: op_name}`` of every instruction of a compiled
    program's HLO text that carries one."""
    out = {}
    for line in hlo_text.splitlines():
        name = instruction(line)
        if name:
            m = _OP_NAME.search(line)
            if m:
                out[name] = m.group(1)
    return out


def scope(op_name: str) -> Optional[str]:
    """The innermost ``repro.graph.<scope>`` of an op_name path."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else None


def direction(instr: str) -> Optional[str]:
    """``fprop``, ``dgrad`` or ``wgrad`` for a conv kernel inlined from that
    plan executor, else None."""
    for d in DIRECTIONS:
        if f"_exec_{d}" in instr:
            return d
    return None


def reduce_planes(planes, names: Dict[str, str], chips: int = 1) -> Optional[dict]:
    """Seconds of device time in the window, averaged over the chips used:
    ``graph_s`` (ops whose scope is bn, pool or add), ``scope_s`` (per
    scope, every ``repro.graph`` scope), ``conv_dir_s`` (conv kernels per
    direction).  None when the trace holds no device operation."""
    planes = list(planes)
    devices = sorted(
        (p for p in planes if p.name.startswith(trace_reduce.DEVICE_PREFIX)),
        key=lambda p: int(p.name[len(trace_reduce.DEVICE_PREFIX):]))[:chips]
    ops = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
           for plane in devices for line in plane.lines
           if line.name == trace_reduce.OPS_LINE for ev in line.events]
    if not ops:
        return None
    w0, w1 = trace_reduce._window(trace_reduce._host_events(planes),
                                  [(s, e, n, False) for s, e, n in ops])
    scope_s: Dict[str, float] = {}
    conv_dir_s = {d: 0.0 for d in DIRECTIONS}
    n = len(devices)
    for s, e, text in ops:
        if e <= w0 or s >= w1:
            continue
        d = (min(e, w1) - max(s, w0)) * 1e-9 / n
        instr = instruction(text)
        sc = scope(names.get(instr, ""))
        if sc is not None:
            scope_s[sc] = scope_s.get(sc, 0.0) + d
        if trace_reduce.is_conv(text):
            kind = direction(instr)
            if kind is not None:
                conv_dir_s[kind] += d
    return {"graph_s": sum(scope_s.get(g, 0.0) for g in GRAPH_SCOPES),
            "scope_s": scope_s, "conv_dir_s": conv_dir_s}


def reduce_dir(trace_dir: str, hlo_text: str, chips: int = 1) -> Optional[dict]:
    """``reduce_planes`` of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = trace_reduce.trace_files(trace_dir)
    if not files:
        return None
    path = max(files, key=os.path.getmtime)
    return reduce_planes(ProfileData.from_file(path).planes,
                         op_names(hlo_text), chips)
