"""The one VMEM-footprint formula of the stack.

Every consumer of "does this blocking fit on-chip memory" answers it here:

  * ``core/mapping._score`` rejects over-budget candidates during selection;
  * ``tune/space.enumerate_space`` filters the autotuner's search space;
  * ``kernels/mg3m_conv`` refuses to launch an over-budget blocking;
  * ``analysis/verify`` re-checks every built plan statically;
  * ``strip_width`` picks the dense-route strip from it (below).

Before this module the arithmetic lived in ``core/mapping`` and the kernels
trusted selection to have done it — a drifted copy (or a caller bypassing
selection) could launch a blocking whose working set Mosaic cannot
double-buffer.  Keeping the formula here, importable from everywhere
(this module depends only on ``core.scene``), makes the agreement
structural instead of conventional.

The model per schedule (see ``core/mapping`` for the schedule semantics):

  TB11  whole FLT + the input window of a strip of ``bw`` output columns
        ((bw-1)*stdW + 1 columns of (K, N)) + (bw, M, N) output;
  TB18  an OC-slice of FLT (bm wide) + the same window + (bw, bm, N) output;
  TB88  classic (bm x bk) x (bk x bn) GEMM tiles, one pixel a step;
  WBL   the batch-on-lanes weight gradient (``batch_lanes_vmem_bytes``):
        the whole dFLT as a resident (taps x IC rows, OC) accumulator, the
        input window of a strip of ``bw`` dOUT columns and the dOUT strip,
        with the batch on the lanes.

Streamed operands are double-buffered (x2, the paper's Alg. 3 analogue —
Mosaic overlaps the next block's DMA with compute), plus a persistent fp32
accumulator of one tile per strip column.

Two counts.  The plain one (array bytes) is what the selector and the tuner
price, one pixel a step (``bw=1``).  The tiled one rounds each block's two
minor dims up to the chip's (sublane, 128-lane) tile, as Mosaic lays them
out in VMEM: a batch of 8 occupies 128 lanes.  A strip wider than one
column is admitted on the tiled count (``launch_vmem_bytes``), so a strip
never takes a launch that fits today past what the chip holds; ``bw=1`` is
the launch the selector chose, held to its plain count as before.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.scene import ConvScene, round_up

__all__ = ["VMEM_BUDGET", "STRIP_CAP", "vmem_bytes", "launch_vmem_bytes",
           "strip_width", "sublane_rows", "batch_lanes_blocks",
           "batch_lanes_vmem_bytes", "batch_lanes_strip"]

# Leave headroom for Mosaic's double buffering (the paper's Alg.3 analogue
# happens automatically: in-flight copies need the second buffer).
VMEM_BUDGET = 12 * 2 ** 20
# Widest strip of output columns one TB11/TB18 grid step computes.
STRIP_CAP = 32
LANE = 128    # minor-dim tile
SUBLANE = 8   # second-minor tile (fp32)


def sublane_rows(itemsize: int) -> int:
    """Rows of one sublane tile: a sublane holds 32 bits, so 8 rows of
    f32 and 16 of bf16."""
    return SUBLANE * 4 // itemsize


def _block_bytes(lead: int, rows: int, cols: int, itemsize: int,
                 tiled: bool) -> int:
    """Bytes of a ``(lead, rows, cols)`` block, its minor two dims rounded
    to the (sublane, lane) tile when ``tiled``."""
    if tiled:
        rows = round_up(rows, sublane_rows(itemsize))
        cols = round_up(cols, LANE)
    return lead * rows * cols * itemsize


def vmem_bytes(scene: ConvScene, schedule: str, bm: int, bn: int,
               bk: int, bw: int = 1, *, tiled: bool = False) -> int:
    """VMEM working-set bytes of one grid step of ``schedule`` at blocking
    ``(bm, bn, bk)`` and strip width ``bw`` over ``scene`` —
    double-buffered operands + fp32 accumulator, in array bytes or, with
    ``tiled``, in the chip's tiled layout.  Pure integer arithmetic;
    raises ``ValueError`` on an unknown schedule."""
    it = jnp.dtype(scene.dtype).itemsize
    win = (bw - 1) * scene.stdW + 1  # input columns under the strip
    taps = scene.fltH * scene.fltW
    if schedule == "TB11":
        flt, inp, out = ((taps, scene.K, scene.M), (win, scene.K, scene.N),
                         (bw, scene.M, scene.N))
    elif schedule == "TB18":
        flt, inp, out = ((taps, scene.K, bm), (win, scene.K, scene.N),
                         (bw, bm, scene.N))
    elif schedule == "TB88":
        flt, inp, out = (1, bk, bm), (1, bk, bn), (1, bm, bn)
    else:
        raise ValueError(f"unknown schedule {schedule!r}")
    streamed = sum(_block_bytes(*blk, it, tiled) for blk in (flt, inp, out))
    acc = _block_bytes(bw, bm, bn, 4, tiled)  # fp32 accumulator scratch
    # x2: Mosaic double-buffers streamed operands (paper Alg. 3).
    return 2 * streamed + acc


def launch_vmem_bytes(scene: ConvScene, schedule: str, bm: int, bn: int,
                      bk: int, bw: int) -> int:
    """The count a launch at strip width ``bw`` is held to: the plain one
    at ``bw=1`` (the selector's), the tiled one for a strip."""
    return vmem_bytes(scene, schedule, bm, bn, bk, bw, tiled=bw > 1)


def strip_width(scene: ConvScene, schedule: str, bm: int, bn: int,
                bk: int) -> int:
    """Output columns one grid step of ``schedule`` computes: the largest
    divisor of ``outW`` up to ``STRIP_CAP`` whose tiled working set fits
    ``VMEM_BUDGET``.  1 for TB88 and for the sentinel route (lhs-dilated
    scenes), where a strip's taps are not contiguous input columns."""
    if schedule == "TB88" or scene.dilH > 1 or scene.dilW > 1:
        return 1
    for bw in range(min(STRIP_CAP, scene.outW), 1, -1):
        if (scene.outW % bw == 0
                and launch_vmem_bytes(scene, schedule, bm, bn, bk, bw)
                <= VMEM_BUDGET):
            return bw
    return 1


def batch_lanes_blocks(scene: ConvScene, bn: int, bw: int):
    """``(lead, rows, cols)`` of the input window, dOUT strip and resident
    dFLT blocks of one WBL step over the *forward* ``scene``: IC padded to
    a sublane tile (``kp``), ``bn`` batch lanes, a strip of ``bw`` dOUT
    columns whose window spans ``(bw-1)*stdW + fltW`` input columns."""
    kp = round_up(scene.IC, sublane_rows(jnp.dtype(scene.dtype).itemsize))
    win = (bw - 1) * scene.stdW + scene.fltW
    taps = scene.fltH * scene.fltW
    return ((scene.fltH * win, kp, bn), (bw, scene.OC, bn),
            (1, taps * kp, scene.OC))


def batch_lanes_vmem_bytes(scene: ConvScene, bn: int, bw: int) -> int:
    """Tiled VMEM bytes of one WBL step: the three blocks double-buffered
    plus the fp32 dFLT accumulator."""
    it = jnp.dtype(scene.dtype).itemsize
    blocks = batch_lanes_blocks(scene, bn, bw)
    streamed = sum(_block_bytes(*blk, it, True) for blk in blocks)
    return 2 * streamed + _block_bytes(*blocks[2], 4, True)


def batch_lanes_strip(scene: ConvScene, bn: int) -> int:
    """dOUT columns one WBL step contracts: the largest divisor of
    ``outW`` up to ``STRIP_CAP`` whose working set fits ``VMEM_BUDGET``;
    0 when not even one column fits."""
    for bw in range(min(STRIP_CAP, scene.outW), 0, -1):
        if (scene.outW % bw == 0
                and batch_lanes_vmem_bytes(scene, bn, bw) <= VMEM_BUDGET):
            return bw
    return 0
