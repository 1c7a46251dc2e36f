"""Static plan/schedule verifier — proves launch-geometry properties with
pure integer math, no kernel execution.

The kernels describe every launch as a ``KernelGridSpec`` (grid extents,
block shapes, index maps — see ``kernels/mg3m_conv.kernel_grid_spec``).
This module abstractly evaluates that spec over the full grid, vectorized
with numpy broadcasting over sparse coordinate axes, and checks:

  (a) output coverage and disjointness — the output blocks (strips of
      ``spec.strip`` columns on TB11/TB18) written by the parallel subgrid
      tile the output exactly once, and no reduction axis moves the output
      block (a moved block means a lost accumulation);
  (b) operand index maps in bounds (element offsets, whether the map
      returns block indices or ``pl.Element`` offsets), and every pixel of
      a strip reads its own tap: pixel ``p`` reads window column
      ``p * stdW``, which must be the specification's column for output
      column ``strip * ow + p``.  On lhs-dilated scenes, sentinel
      resolution: every dilation-hole / out-of-range tap reads exactly the
      designated zero row/col, every live tap reads its real element.  The
      expected map is *recomputed here from the scene definition*, on
      purpose: the kernel's own index map is the implementation under test,
      so sharing its code would verify nothing (N-version programming);
  (c) VMEM footprint within budget via the one shared
      ``analysis.footprint`` formula;
  (d) dtype promotion — the accumulator must hold the IO dtype's promotion
      (fp32-or-wider float);
  (e) grid-step and MAC agreement with the cost model's closed forms
      (``mapping.grid_steps`` / ``scene.macs``), so the tuner's search
      space, the cost model, and the kernels cannot silently disagree.  The
      cost model counts pixel-steps: grid steps x strip width.

The WBL weight gradient launches a ``BatchLanesGridSpec`` instead, whose
grid walks only the contraction; ``check_batch_lanes_spec`` proves that
every (oh, ow, lane) term of dOUT is visited once and meets every filter
tap at the input element the definition names, inside the fetched window
and the launched input, and that the VMEM count is the footprint's.

Findings are data (``Finding``), never exceptions: the verifier's job is
to report every violated property of a geometry, including geometries the
kernel constructors would refuse to build.

Entry points: ``verify_point`` (scene + schedule + blocks),
``verify_choice`` (a ``ScheduleChoice``), ``verify_plan`` (a built
``ConvPlan``), and ``sweep_scene``/``sweep_scenes`` (every feasible
schedule of every op of a scene list — the CI gate, see
``scripts/analyze.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

import numpy as np
import jax.numpy as jnp

from repro.analysis.footprint import (_block_bytes, batch_lanes_vmem_bytes,
                                      launch_vmem_bytes, sublane_rows)
from repro.core.mapping import (BATCH_LANES, VMEM_BUDGET, ScheduleChoice,
                                grid_steps)
from repro.core.scene import ConvScene
from repro.kernels.mg3m_conv import (BatchLanesGridSpec, KernelGridSpec,
                                     batch_lanes_grid_spec, kernel_grid_spec)
from repro.plan.build import (ConvOp, ConvPlan, derive_exec_spec,
                              grad_filter_scene, grad_input_scene,
                              launched_shapes, _dgrad_blocker, _wgrad_blocker)

__all__ = ["Finding", "verify_point", "verify_choice", "verify_plan",
           "verify_sharded_plan", "sweep_scene", "sweep_scenes",
           "check_spec", "check_batch_lanes_spec"]


@dataclasses.dataclass(frozen=True)
class Finding:
    """One violated property of a launch geometry.

    ``severity`` is "error" (the geometry computes a wrong answer or cannot
    run) or "warn" (a documented cost-model approximation).  ``message`` is
    self-contained: it names the scene, schedule, blocking, and the first
    offending grid coordinate where one exists.
    """

    code: str
    severity: str
    message: str
    scene: str
    schedule: str
    blocks: Tuple[int, int, int]
    op: str = ""

    @property
    def is_error(self) -> bool:
        return self.severity == "error"


def errors(findings: Iterable[Finding]) -> List[Finding]:
    return [f for f in findings if f.is_error]


# --------------------------------------------------------------------------
# abstract grid evaluation
# --------------------------------------------------------------------------
def _sparse_coords(grid: Tuple[int, ...]) -> List[np.ndarray]:
    """Sparse (broadcastable) coordinate arrays for every grid axis.

    Index maps evaluated on these stay small wherever they are separable —
    an array only grows along the axes the map actually combines — while
    remaining exact for arbitrary (non-separable) maps via broadcasting.
    """
    return list(np.meshgrid(*[np.arange(e, dtype=np.int64) for e in grid],
                            indexing="ij", sparse=True))


def _eval_map(fn, coords, grid: Tuple[int, ...]) -> List[np.ndarray]:
    """Evaluate an index map over the whole grid; each returned component is
    broadcast to the full grid shape (a view, not a copy)."""
    out = fn(*coords)
    return [np.broadcast_to(np.asarray(c), grid) for c in out]


def _first_coord(mask: np.ndarray) -> Tuple[int, ...]:
    """First grid coordinate where ``mask`` is True (for messages)."""
    return tuple(int(x) for x in np.argwhere(mask)[0])


def _expected_spatial(scene: ConvScene, axis: str
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """The *specification* of the spatial index map along one axis, as an
    ``(n_out, n_tap)`` table of (index, live) — recomputed from the scene
    definition, independent of the kernel's implementation.

    Dense route (no lhs dilation): the launched input is pre-padded, tap
    ``(o, t)`` reads padded row ``o*std + t*fdil`` and every tap is live.
    Sentinel route: the compact input keeps its real extent plus one zero
    row/col at ``in_real``; a tap is live iff it lands on a stored element
    of the virtually padded+dilated input, else it must read the sentinel.
    """
    if axis == "h":
        n_out, n_tap = scene.outH, scene.fltH
        std, fdil, dil = scene.stdH, scene.fdilH, scene.dilH
        pad, in_real = scene.padH, scene.inH
    else:
        n_out, n_tap = scene.outW, scene.fltW
        std, fdil, dil = scene.stdW, scene.fdilW, scene.dilW
        pad, in_real = scene.padW, scene.inW
    o = np.arange(n_out, dtype=np.int64)[:, None]
    t = np.arange(n_tap, dtype=np.int64)[None, :]
    p = o * std + t * fdil
    # The route is a property of the whole scene, not of one axis: any lhs
    # dilation puts BOTH axes on the compact (unpadded) input + sentinel.
    if scene.dilH == 1 and scene.dilW == 1:
        return p, np.ones_like(p, dtype=bool)
    q = p - pad
    live = (q >= 0) & (q % dil == 0) & (q < in_real * dil)
    return np.where(live, q // dil, in_real), live


def _table_on_grid(table: np.ndarray, grid: Tuple[int, ...],
                   out_dim: int, tap_dim: int) -> np.ndarray:
    """Broadcast an (n_out, n_tap) spec table over the full grid, placing
    its axes at grid dims ``out_dim``/``tap_dim`` (truncated to the grid's
    actual extents so a dropped-tap grid still walks)."""
    table = table[:grid[out_dim], :grid[tap_dim]]
    t = table if out_dim < tap_dim else table.T
    shape = [1] * len(grid)
    shape[out_dim] = table.shape[0]
    shape[tap_dim] = table.shape[1]
    return np.broadcast_to(t.reshape(shape), grid)


def _check_strip_pixel(out: List[Finding], finding, scene: ConvScene,
                       p: int, *, got_h: np.ndarray, got_w: np.ndarray,
                       want_h: np.ndarray, want_w: np.ndarray,
                       live_g: np.ndarray) -> None:
    """Compare the input row/col strip pixel ``p`` reads at every grid
    step with the specification's, appending findings to ``out``.  Dense
    route: exact agreement.  Sentinel route: a *live* tap (both axes land
    on stored elements) must read exactly its real (row, col); a *dead* tap
    (either axis is a dilation hole / out of range) must read zeros, i.e.
    point at least one coordinate at the zero sentinel — matching the
    kernel's combined H-and-W liveness is not required, matching zeroness
    is."""
    px = f" (strip pixel {p})" if p else ""
    if scene.dilH == 1 and scene.dilW == 1:
        for dim, got, want in ((0, got_h, want_h), (1, got_w, want_w)):
            neq = got != want
            if neq.any():
                c = _first_coord(neq)
                out.append(finding(
                    "index-map-mismatch",
                    f"input spatial index dim {dim} at grid{c}{px} is "
                    f"{int(got[c])}, specification says {int(want[c])}"))
        return
    sent_h, sent_w = scene.inH, scene.inW
    at_sent = (got_h == sent_h) | (got_w == sent_w)
    dropped = live_g & at_sent
    if dropped.any():
        c = _first_coord(dropped)
        out.append(finding(
            "dropped-tap",
            f"live tap at grid{c}{px} resolves to the zero sentinel "
            f"({sent_h}, {sent_w}) instead of row/col "
            f"({int(want_h[c])}, {int(want_w[c])}); its "
            f"contribution is dropped"))
    mism = live_g & ~at_sent & ((got_h != want_h) | (got_w != want_w))
    if mism.any():
        c = _first_coord(mism)
        out.append(finding(
            "index-map-mismatch",
            f"live tap at grid{c}{px} reads "
            f"({int(got_h[c])}, {int(got_w[c])}), specification "
            f"says ({int(want_h[c])}, {int(want_w[c])})"))
    miss = ~live_g & ~at_sent
    if miss.any():
        c = _first_coord(miss)
        out.append(finding(
            "sentinel-miss",
            f"dilation-hole/out-of-range tap at grid{c}{px} reads "
            f"live ({int(got_h[c])}, {int(got_w[c])}) instead of "
            f"the zero sentinel row/col; the hole contributes "
            f"garbage"))


# --------------------------------------------------------------------------
# the checks
# --------------------------------------------------------------------------
def check_spec(spec: KernelGridSpec, *, vmem_budget: int = VMEM_BUDGET,
               op: str = "") -> List[Finding]:
    """Verify every static property of one launch geometry.  Returns all
    findings (never raises on a bad geometry)."""
    scene = spec.scene
    where = (f"{spec.schedule}@{spec.blocks[0]}/{spec.blocks[1]}/"
             f"{spec.blocks[2]} on {scene.describe()}")

    def finding(code: str, msg: str, severity: str = "error") -> Finding:
        return Finding(code=code, severity=severity,
                       message=f"{msg} [{where}]", scene=scene.describe(),
                       schedule=spec.schedule, blocks=spec.blocks, op=op)

    out: List[Finding] = []

    # -- structural bookkeeping ------------------------------------------
    if len(spec.dimension_semantics) != len(spec.grid):
        out.append(finding(
            "grid-structure",
            f"dimension_semantics arity {len(spec.dimension_semantics)} != "
            f"grid rank {len(spec.grid)}"))
        return out
    for d in spec.reduction_dims:
        if spec.dimension_semantics[d] != "arbitrary":
            out.append(finding(
                "grid-structure",
                f"reduction grid dim {d} is marked "
                f"{spec.dimension_semantics[d]!r}; a parallel reduction "
                f"axis races on the accumulator"))
    got_red = tuple(spec.grid[d] for d in spec.reduction_dims)
    if got_red != spec.reduction_extents:
        out.append(finding(
            "grid-structure",
            f"reduction_extents {spec.reduction_extents} disagree with the "
            f"grid's reduction dims {got_red}; the kernel body would "
            f"init/store on the wrong reduction step"))
    oh_ow = (spec.grid[spec.spatial_dims[0]],
             spec.grid[spec.spatial_dims[1]] * spec.strip)
    if oh_ow != (scene.outH, scene.outW):
        out.append(finding(
            "grid-structure",
            f"grid spatial extents {oh_ow} (strip {spec.strip}) != scene "
            f"output ({scene.outH}, {scene.outW})"))
    if spec.out_block[:2] != (1, spec.strip):
        out.append(finding(
            "grid-structure",
            f"output spatial block {spec.out_block[:2]} is not one row of "
            f"a {spec.strip}-column strip"))
    taps = tuple(spec.grid[d] for d in spec.tap_dims)
    if taps != (scene.fltH, scene.fltW):
        out.append(finding(
            "dropped-tap",
            f"grid tap extents {taps} != filter taps "
            f"({scene.fltH}, {scene.fltW}); missing taps silently drop "
            f"their contribution"))
    if spec.flt_shape[:2] != (scene.fltH, scene.fltW):
        out.append(finding(
            "grid-structure",
            f"launched filter spatial dims {spec.flt_shape[:2]} != scene "
            f"filter ({scene.fltH}, {scene.fltW})"))
    for d in range(4):
        if spec.out_shape[d] % spec.out_block[d]:
            out.append(finding(
                "grid-structure",
                f"output dim {d} ({spec.out_shape[d]}) not divisible by "
                f"its block ({spec.out_block[d]})"))
    if any(f.code == "grid-structure" for f in out):
        return out  # geometry too malformed for the walks below

    # -- abstract walk ----------------------------------------------------
    coords = _sparse_coords(spec.grid)
    o_idx = _eval_map(spec.out_index, coords, spec.grid)
    i_idx = _eval_map(spec.in_index, coords, spec.grid)
    f_idx = _eval_map(spec.flt_index, coords, spec.grid)

    # (a) reduction steps must revisit, never move, the output block
    red0 = tuple(0 if d in spec.reduction_dims else slice(None)
                 for d in range(len(spec.grid)))
    red_keep = tuple(slice(0, 1) if d in spec.reduction_dims else slice(None)
                     for d in range(len(spec.grid)))
    for d, comp in enumerate(o_idx):
        moved = comp != comp[red_keep]  # keepdims slice re-broadcasts
        if moved.any():
            c = _first_coord(moved)
            out.append(finding(
                "reduction-dependence",
                f"output block index dim {d} changes across reduction "
                f"steps (first at grid{c}); the accumulation chain is "
                f"split and partial sums overwrite each other"))
    if any(f.code == "reduction-dependence" for f in out):
        return out

    # (a) coverage + disjointness of the parallel subgrid
    exts = tuple(s // b for s, b in zip(spec.out_shape, spec.out_block))
    par = [o_idx[d][red0] for d in range(4)]
    oob = np.zeros(par[0].shape, dtype=bool)
    for d in range(4):
        oob |= (par[d] < 0) | (par[d] >= exts[d])
    if oob.any():
        c = _first_coord(oob)
        vals = tuple(int(p[c]) for p in par)
        out.append(finding(
            "out-coverage",
            f"output block index {vals} out of range {exts} at parallel "
            f"grid{c}; the write lands outside the output"))
    else:
        lin = par[0].astype(np.int64)
        for d in range(1, 4):
            lin = lin * exts[d] + par[d]
        n_tiles = int(np.prod(exts))
        uniq = np.unique(lin)
        if lin.size > uniq.size:
            out.append(finding(
                "out-overlap",
                f"{lin.size - uniq.size} duplicate output-block writes "
                f"across the parallel subgrid; overlapping stores race"))
        if uniq.size < n_tiles:
            out.append(finding(
                "out-coverage",
                f"only {uniq.size} of {n_tiles} output blocks are written; "
                f"uncovered output stays uninitialized"))

    # (b) operand bounds, on element offsets
    i_off = [c if spec.in_elements else c * b
             for c, b in zip(i_idx, spec.in_block)]
    f_off = [c * b for c, b in zip(f_idx, spec.flt_block)]
    for nm, off, blocks, shape in (("input", i_off, spec.in_block,
                                    spec.in_shape),
                                   ("filter", f_off, spec.flt_block,
                                    spec.flt_shape)):
        for d in range(4):
            bad = (off[d] < 0) | (off[d] + blocks[d] > shape[d])
            if bad.any():
                c = _first_coord(bad)
                out.append(finding(
                    f"{'in' if nm == 'input' else 'flt'}-bounds",
                    f"{nm} index map dim {d} reads elements "
                    f"[{int(off[d][c])}, {int(off[d][c]) + blocks[d]}) "
                    f"outside the launched dim {shape[d]} at grid{c}"))

    # (b) contraction / tiling alignment: the K slice both operands read,
    # and the M/N slices operands and output carry, must agree per step
    pairs = (("contraction K", i_off[2], f_off[2]),
             ("output M", o_idx[2] * spec.out_block[2], f_off[3]),
             ("output N", o_idx[3] * spec.out_block[3], i_off[3]))
    for nm, a, b in pairs:
        neq = a != b
        if neq.any():
            c = _first_coord(neq)
            out.append(finding(
                "operand-misalign",
                f"{nm} element offsets disagree at grid{c}: "
                f"{int(a[c])} vs {int(b[c])}; the step multiplies/stores "
                f"mismatched slices"))

    # (b) spatial map vs the recomputed specification, pixel by pixel of
    # the strip (``_check_strip_pixel``).
    want_h_tab, live_h = _expected_spatial(scene, "h")
    want_w_tab, live_w = _expected_spatial(scene, "w")
    live_tabs = (live_h, live_w)
    win = (spec.strip - 1) * scene.stdW + 1
    if spec.in_block[:2] != (1, win):
        out.append(finding(
            "grid-structure",
            f"input spatial block {spec.in_block[:2]} != (1, {win}): one "
            f"tap row and the {spec.strip}-column strip's window"))
    else:
        place = lambda tab, dim: _table_on_grid(  # noqa: E731
            tab, spec.grid, spec.spatial_dims[dim], spec.tap_dims[dim])
        want_h = place(want_h_tab, 0)
        for p in range(spec.strip):
            _check_strip_pixel(
                out, finding, scene, p, got_h=i_off[0],
                got_w=i_off[1] + p * scene.stdW, want_h=want_h,
                want_w=place(want_w_tab[p::spec.strip], 1),
                live_g=place(live_h, 0) & place(live_w[p::spec.strip], 1))

    # (b) every tap's filter row/col must be inside the fetched flt block
    for dim in (0, 1):
        tap = coords[spec.tap_dims[dim]]
        lo = f_idx[dim] * spec.flt_block[dim]
        bad = (tap < lo) | (tap >= lo + spec.flt_block[dim])
        if bad.any():
            c = _first_coord(np.broadcast_to(bad, spec.grid))
            out.append(finding(
                "flt-bounds",
                f"filter tap dim {dim} at grid{c} lies outside the "
                f"fetched filter block"))

    # (c) VMEM budget — the one shared footprint formula
    need = launch_vmem_bytes(scene, spec.schedule, *spec.blocks,
                             spec.strip)
    if need > vmem_budget:
        out.append(finding(
            "vmem-overshoot",
            f"blocking needs {need} B of VMEM, budget is {vmem_budget} B; "
            f"Mosaic cannot double-buffer this working set"))

    # (d) accumulator must hold the IO dtype's promotion
    acc = jnp.dtype(spec.acc_dtype)
    io = jnp.dtype(scene.dtype)
    if (acc.kind != "f" or acc.itemsize < 4
            or jnp.promote_types(io, acc) != acc):
        out.append(finding(
            "dtype-promotion",
            f"accumulator dtype {acc.name} cannot hold the promotion of "
            f"IO dtype {io.name}; partial sums lose precision across "
            f"reduction steps"))

    # (e) agreement with the cost model's closed forms
    steps = int(np.prod(spec.grid)) * spec.strip
    want_steps = grid_steps(scene, *spec.blocks)
    if steps != want_steps:
        out.append(finding(
            "grid-steps-disagree",
            f"grid walk has {steps} pixel-steps ({spec.strip} a grid "
            f"step), cost model's closed form says "
            f"{want_steps}; predicted overhead/compute diverge from the "
            f"launch"))
    walk_macs = (scene.M * scene.N * scene.K
                 * int(live_tabs[0].sum()) * int(live_tabs[1].sum()))
    if scene.dilH == 1 and scene.dilW == 1:
        if walk_macs != scene.macs:
            out.append(finding(
                "mac-disagree",
                f"grid walk counts {walk_macs} useful MACs, closed-form "
                f"scene.macs says {scene.macs}"))
    elif walk_macs > scene.macs:
        # scene.macs uses the per-row upper bound ceil(flt/dil) taps; a
        # walk exceeding it means the closed form *under*counts real work.
        out.append(finding(
            "mac-disagree",
            f"grid walk counts {walk_macs} useful MACs, above closed-form "
            f"scene.macs {scene.macs}; the cost model undercounts this "
            f"dilated scene", severity="warn"))

    return out


def check_batch_lanes_spec(spec: BatchLanesGridSpec, *,
                           vmem_budget: int = VMEM_BUDGET,
                           op: str = "wgrad") -> List[Finding]:
    """Verify a WBL launch over its forward scene.  Every grid axis is a
    contraction, so the output block must never move; the dOUT blocks
    must tile dOUT (every (oh, ow, lane) term once); strip pixel ``p``'s
    tap (i, j) — window row i, column ``p*col_stride + j`` — must lie in
    the fetched window and be input element
    ``(oh*stdH + i, ow*stdW + j)`` on the dOUT pixel's lanes, recomputed
    here from the scene; the window must stay inside the launched input;
    and the blocks' VMEM bytes must be ``footprint``'s count, within
    budget."""
    scene = spec.scene
    bn, bw = spec.in_block[3], spec.strip
    where = f"{BATCH_LANES}@{bn}x{bw} on {scene.describe()}"
    blocks = (scene.OC, bn, spec.in_block[2])

    def finding(code: str, msg: str) -> Finding:
        return Finding(code=code, severity="error",
                       message=f"{msg} [{where}]", scene=scene.describe(),
                       schedule=BATCH_LANES, blocks=blocks, op=op)

    out: List[Finding] = []
    kp = spec.in_shape[2]
    if (spec.taps != (scene.fltH, scene.fltW)
            or spec.out_shape != (scene.fltH * scene.fltW * kp, scene.OC)
            or kp < scene.IC):
        out.append(finding(
            "dropped-tap",
            f"taps {spec.taps} into a {spec.out_shape} dFLT at {kp} "
            f"channel rows do not cover the ({scene.fltH}, {scene.fltW}) "
            f"filter's {scene.IC} channels"))
    if spec.dout_block[:2] != (1, bw) or spec.col_stride != scene.stdW:
        out.append(finding(
            "grid-structure",
            f"dOUT block {spec.dout_block[:2]} with column stride "
            f"{spec.col_stride} is not one row of a {bw}-column strip "
            f"read {scene.stdW} input columns apart"))
    if out:
        return out

    coords = _sparse_coords(spec.grid)
    o_idx = _eval_map(spec.out_index, coords, spec.grid)
    if any((c != 0).any() for c in o_idx):
        out.append(finding(
            "reduction-dependence",
            "the dFLT block moves across the grid; every axis contracts "
            "into it"))
    d_idx = _eval_map(spec.dout_index, coords, spec.grid)
    i_off = _eval_map(spec.in_index, coords, spec.grid)

    # every (oh, ow-strip, lane block) of dOUT exactly once
    exts = tuple(s // b for s, b in zip(spec.dout_shape, spec.dout_block))
    oob = np.zeros(spec.grid, dtype=bool)
    for d in range(4):
        oob |= (d_idx[d] < 0) | (d_idx[d] >= exts[d])
    if oob.any():
        out.append(finding(
            "out-coverage",
            f"dOUT block index out of range {exts} at "
            f"grid{_first_coord(oob)}"))
        return out
    lin = d_idx[0].astype(np.int64)
    for d in range(1, 4):
        lin = lin * exts[d] + d_idx[d]
    uniq = np.unique(lin)
    if uniq.size != lin.size or uniq.size != int(np.prod(exts)):
        out.append(finding(
            "out-coverage",
            f"the grid visits {uniq.size} distinct of {int(np.prod(exts))} "
            f"dOUT blocks in {lin.size} steps; a (pixel, lane) term is "
            f"dropped or counted twice"))

    # the window inside the launched input
    for d in range(4):
        bad = (i_off[d] < 0) | (i_off[d] + spec.in_block[d]
                                > spec.in_shape[d])
        if bad.any():
            c = _first_coord(bad)
            out.append(finding(
                "in-bounds",
                f"input window dim {d} reads elements [{int(i_off[d][c])}, "
                f"{int(i_off[d][c]) + spec.in_block[d]}) outside the "
                f"launched dim {spec.in_shape[d]} at grid{c}"))
    if (i_off[2] != 0).any() or spec.in_block[2] != kp:
        out.append(finding(
            "operand-misalign",
            "the window does not carry every channel row of the input"))
    lanes = i_off[3] != d_idx[3] * spec.dout_block[3]
    if lanes.any() or spec.in_block[3] != spec.dout_block[3]:
        c = _first_coord(lanes) if lanes.any() else ()
        out.append(finding(
            "operand-misalign",
            f"input and dOUT lanes disagree at grid{c}: the batch terms "
            f"of the contraction are mismatched"))

    # every tap of every strip pixel at the element the definition names:
    # pixel p reads window rows 0..fh-1 and columns p*col_stride + 0..fw-1
    fh, fw = spec.taps
    span = (bw - 1) * spec.col_stride + fw
    if fh > spec.in_block[0] or span > spec.in_block[1]:
        out.append(finding(
            "window-bounds",
            f"the strip's taps span {fh} rows x {span} columns, the "
            f"fetched window holds {spec.in_block[:2]}; the last taps "
            f"read past it"))
    checks = [("row", 0, i_off[0], d_idx[0] * scene.stdH)]
    checks += [("column", p, i_off[1] + p * spec.col_stride,
                (d_idx[1] * bw + p) * scene.stdW) for p in range(bw)]
    for axis, p, got, want in checks:
        bad = got != want
        if bad.any():
            c = _first_coord(bad)
            out.append(finding(
                "index-map-mismatch",
                f"strip pixel {p}'s tap 0 {axis} at grid{c} is input "
                f"{axis} {int(got[c])}, the definition says "
                f"{int(want[c])}"))

    # (c) the blocks' VMEM bytes are the footprint's count
    it = jnp.dtype(scene.dtype).itemsize
    streamed = (_block_bytes(spec.in_block[0] * spec.in_block[1],
                             *spec.in_block[2:], it, True)
                + _block_bytes(bw, *spec.dout_block[2:], it, True)
                + _block_bytes(1, *spec.out_block, it, True))
    got = 2 * streamed + _block_bytes(1, *spec.acc_shape, 4, True)
    want = batch_lanes_vmem_bytes(scene, bn, bw)
    if got != want:
        out.append(finding(
            "vmem-disagree",
            f"the launched blocks need {got} B of VMEM, footprint counts "
            f"{want} B"))
    if max(got, want) > vmem_budget:
        out.append(finding(
            "vmem-overshoot",
            f"needs {max(got, want)} B of VMEM, budget is {vmem_budget} B"))
    if kp % sublane_rows(it):
        out.append(finding(
            "operand-misalign",
            f"{kp} channel rows are not whole sublane tiles: the taps' "
            f"slabs do not stack"))
    return out


# --------------------------------------------------------------------------
# entry points
# --------------------------------------------------------------------------
def _spec_for(scene: ConvScene, choice: ScheduleChoice,
              out_hw: Optional[Tuple[int, int]] = None
              ) -> Tuple[Optional[KernelGridSpec], Optional[Finding]]:
    spec = derive_exec_spec(scene, choice, out_hw)
    in_shape, flt_shape = launched_shapes(scene, spec)
    try:
        kspec = kernel_grid_spec(scene, choice.schedule, in_shape=in_shape,
                                 flt_shape=flt_shape, bm=spec.bm, bn=spec.bn,
                                 bk=spec.bk, bw=spec.bw, vmem_budget=0)
    except ValueError as e:
        return None, Finding(
            code="spec-invalid", severity="error", message=str(e),
            scene=scene.describe(), schedule=choice.schedule,
            blocks=(choice.bm, choice.bn, choice.bk))
    return kspec, None


def verify_choice(scene: ConvScene, choice: ScheduleChoice, *,
                  vmem_budget: int = VMEM_BUDGET, op: str = ""
                  ) -> List[Finding]:
    """Statically verify one (scene, ScheduleChoice) pair — the geometry a
    plan built from this choice would launch."""
    kspec, bad = _spec_for(scene, choice)
    if bad is not None:
        return [dataclasses.replace(bad, op=op)]
    return check_spec(kspec, vmem_budget=vmem_budget, op=op)


def verify_point(scene: ConvScene, schedule: str, bm: int = 0, bn: int = 0,
                 bk: int = 0, *, vmem_budget: int = VMEM_BUDGET,
                 op: str = "") -> List[Finding]:
    """Statically verify a (schedule, blocking) point over ``scene``.
    TB11 defaults its blocks to the full MM_unit dims."""
    choice = ScheduleChoice(schedule, bm or scene.M, bn or scene.N,
                            bk or scene.K, 0.0, 0.0, 0.0, 0)
    return verify_choice(scene, choice, vmem_budget=vmem_budget, op=op)


def verify_plan(plan: ConvPlan, *, vmem_budget: int = VMEM_BUDGET
                ) -> List[Finding]:
    """Statically verify a built ``ConvPlan``: the stored ``ExecSpec`` must
    re-derive byte-identically from its choice, and the launch geometry
    must pass every ``check_spec`` property.  Reference plans have no
    Pallas geometry — nothing to verify, empty findings."""
    if plan.uses_reference:
        return []
    scene, choice, spec = plan.exec_scene, plan.choice, plan.spec
    out_hw = ((spec.out_h, spec.out_w)
              if (spec.out_h, spec.out_w) != (0, 0) else None)
    want_spec = derive_exec_spec(scene, choice, out_hw)
    if want_spec != spec:
        return [Finding(
            code="spec-mismatch", severity="error",
            message=(f"stored ExecSpec {spec} does not re-derive from its "
                     f"choice (got {want_spec}) for {plan.describe()}"),
            scene=scene.describe(), schedule=choice.schedule,
            blocks=(spec.bm, spec.bn, spec.bk), op=plan.op.value)]
    if choice.schedule == BATCH_LANES:
        in_shape, dout_shape = launched_shapes(scene, spec)
        try:
            kspec = batch_lanes_grid_spec(scene, in_shape=in_shape,
                                          dout_shape=dout_shape, bn=spec.bn,
                                          bw=spec.bw)
        except ValueError as e:
            return [Finding(code="spec-invalid", severity="error",
                            message=str(e), scene=scene.describe(),
                            schedule=BATCH_LANES,
                            blocks=(spec.bm, spec.bn, spec.bk),
                            op=plan.op.value)]
        return check_batch_lanes_spec(kspec, vmem_budget=vmem_budget,
                                      op=plan.op.value)
    return verify_choice(scene, choice, vmem_budget=vmem_budget,
                         op=plan.op.value)


def verify_sharded_plan(plan, *, vmem_budget: int = VMEM_BUDGET
                        ) -> List[Finding]:
    """Statically verify a ``repro.shard.ShardedConvPlan``: the partition
    identity must re-derive from the exec scene (sub-scene, axis
    feasibility, halo row coverage — all integer math), and the inner
    per-shard plan must pass every ``verify_plan`` property on the
    sub-scene.  Collective wiring itself is not statically provable here;
    what *is* provable is that each shard's launch geometry is exactly a
    verified single-device launch and that the shard x sub-scene algebra
    reconstructs the global op."""
    from repro.shard.spec import (halo_geometry, shard_blocker,
                                  shard_sub_scene)
    spec, E = plan.spec, plan.exec_scene
    sch = spec.choice.schedule
    blocks = (spec.choice.bm, spec.choice.bn, spec.choice.bk)

    def finding(code, msg):
        return Finding(code=code, severity="error", message=msg,
                       scene=E.describe(), schedule=sch, blocks=blocks,
                       op=plan.op.value)

    out: List[Finding] = []
    if spec.is_sharded:
        why = shard_blocker(E, spec.axis, spec.n_shards)
        if why:
            out.append(finding(
                "shard-blocked",
                f"partition {spec.tag} is infeasible for "
                f"{E.describe()}: {why}"))
        else:
            want = shard_sub_scene(E, spec.axis, spec.n_shards)
            if spec.sub_scene != want:
                out.append(finding(
                    "shard-sub-scene-mismatch",
                    f"stored sub-scene {spec.sub_scene.describe()} does not "
                    f"re-derive from {E.describe()} under {spec.tag} "
                    f"(expected {want.describe()})"))
            if spec.axis == "h":
                geo = halo_geometry(E, spec.n_shards)
                if spec.n_shards * geo.oh_sub < E.outH:
                    out.append(finding(
                        "halo-coverage",
                        f"{spec.n_shards} shards x {geo.oh_sub} output rows "
                        f"do not cover outH={E.outH}"))
                if spec.sub_scene.outH != geo.oh_sub:
                    out.append(finding(
                        "halo-sub-outH",
                        f"sub-scene outH {spec.sub_scene.outH} != per-shard "
                        f"row count {geo.oh_sub}: the slab height is wrong"))
    elif spec.sub_scene != E:
        out.append(finding(
            "shard-sub-scene-mismatch",
            f"unsharded fallback must execute the exec scene itself, "
            f"stored sub-scene is {spec.sub_scene.describe()}"))
    if plan.inner.exec_scene != spec.sub_scene:
        out.append(finding(
            "shard-inner-scene",
            f"inner plan executes {plan.inner.exec_scene.describe()}, not "
            f"the partition's sub-scene {spec.sub_scene.describe()}"))
    out.extend(verify_plan(plan.inner, vmem_budget=vmem_budget))
    return out


# --------------------------------------------------------------------------
# sweeps (the CI gate)
# --------------------------------------------------------------------------
_ALL_OPS = (ConvOp.FPROP, ConvOp.DGRAD, ConvOp.WGRAD)

_BLOCKERS = {ConvOp.DGRAD: _dgrad_blocker, ConvOp.WGRAD: _wgrad_blocker}
_DERIVE = {ConvOp.FPROP: lambda s: s, ConvOp.DGRAD: grad_input_scene,
           ConvOp.WGRAD: grad_filter_scene}


def sweep_scene(scene: ConvScene, ops: Sequence[ConvOp] = _ALL_OPS, *,
                vmem_budget: int = VMEM_BUDGET
                ) -> Tuple[List[Finding], int]:
    """Verify *every* VMEM-feasible (schedule, blocking) point of every
    requested op of one forward scene — the tuner's whole search space,
    checked without executing a kernel.  Returns (findings, points
    checked).  Ops with no MG3M scene (reference fallbacks) are skipped:
    they have no Pallas geometry."""
    from repro.tune.space import enumerate_space  # local: analysis has no
    # import-time dependency on the tuner (mapping imports analysis back)
    findings: List[Finding] = []
    checked = 0
    for op in ops:
        blocker = _BLOCKERS.get(op)
        if blocker is not None and blocker(scene):
            continue
        exec_scene = _DERIVE[op](scene)
        for pt in enumerate_space(exec_scene, vmem_budget=vmem_budget):
            findings.extend(verify_point(exec_scene, pt.schedule, pt.bm,
                                         pt.bn, pt.bk,
                                         vmem_budget=vmem_budget,
                                         op=op.value))
            checked += 1
    return findings, checked


def sweep_scenes(scenes: Mapping[str, ConvScene],
                 ops: Sequence[ConvOp] = _ALL_OPS, *,
                 vmem_budget: int = VMEM_BUDGET
                 ) -> Tuple[Dict[str, List[Finding]], int]:
    """``sweep_scene`` over a named scene list (e.g.
    ``models.cnn.cnn_layer_scenes``).  Returns ({name: findings}, total
    points checked); names with no findings are omitted."""
    by_name: Dict[str, List[Finding]] = {}
    total = 0
    for name, scene in scenes.items():
        findings, checked = sweep_scene(scene, ops, vmem_budget=vmem_budget)
        total += checked
        if findings:
            by_name[name] = findings
    return by_name, total
