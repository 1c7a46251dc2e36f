"""MG3MConv Pallas TPU kernels — multi-grained implicit-GEMM convolution.

Three grid schedules mirror the paper's TB granularities (see
core/mapping.py for the selection model):

  TB11: grid (outH, outW/bw, fltH, fltW); whole FLT resident in VMEM
        (fetched from HBM exactly once = the paper's outLen->max filter
        reuse), IN window streamed per strip of ``bw`` output columns, fp32
        VMEM accumulator revisited across the (fh, fw) reduction steps.
  TB18: grid (n_m, outH, outW/bw, fltH, fltW); an OC-slice of FLT stays
        resident while the grid sweeps every spatial task.
  TB88: grid (outH, outW, n_m, n_n, fltH, fltW, n_k); classic 2D+K tiled
        GEMM per output pixel.
  WBL:  grid (B/bn, outH, outW/bw); the weight gradient of a forward scene
        whose IC is under a sublane tile, the dual of TB11: the whole dFLT
        is a resident accumulator and the grid walks only the contraction
        (see ``batch_lanes_grid_spec``).

TB11 and TB18 compute a strip of ``bw`` adjacent output columns per grid
step (``analysis.footprint.strip_width``: the largest divisor of outW up
to 32 whose working set fits VMEM).  The step fetches the strip's input
window for its tap — (bw-1)*stdW + 1 columns addressed by element offset —
and pixel ``p`` of the strip contracts window column ``p*stdW``, so every
output pixel accumulates the same dots in the same tap order as a
one-pixel step; the strip only divides the fixed cost of a grid step by
``bw``.  The sentinel route keeps ``bw=1``: its holes make a strip's taps
non-contiguous.

Each launch is described first as a ``KernelGridSpec`` — grid extents,
block shapes, index maps, dimension semantics — built by
``kernel_grid_spec`` and consumed by ``pl.pallas_call``.  The spec is the
single source of truth for the launch geometry: ``repro.analysis.verify``
walks the *same* spec with pure integer math to prove coverage, bounds,
and sentinel resolution statically, so what the verifier checks is what
the kernel runs, not a parallel reimplementation.

Input layout depends on the scene's lhs dilation (see ``_in_index_map``):

  dilH == dilW == 1   a *spatially pre-padded* input [inHp, inWp, K, N]
                      (``plan/build.py`` applies padH/padW/apad and aligns
                      channel dims); tap coordinates index it directly.
  dilH or dilW > 1    the *compact* input [inH+1, inW+1, K, N] with one
                      trailing zero row and column (the sentinel).  The
                      index map folds padding and dilation arithmetic: taps
                      that land on a dilation hole or outside the real
                      extent fetch the sentinel's zeros instead of a memory
                      blowup from host-side zero-interleaving.  This is how
                      the dgrad of a strided forward (a transposed conv)
                      stays on the Pallas fast path.

Filter (rhs) dilation never needs a sentinel: the grid iterates the real
taps only and the index map simply spaces them ``fdil`` apart.  Other
layouts per the paper:
  FLT [fltH, fltW, K, M]   OUT [outH, outW, M, N]
with M=OC, N=B, K=IC.  Accumulation is always fp32 (the TPU analogue of the
paper's DPD kernels), cast to the IO dtype on the final store.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis.footprint import (VMEM_BUDGET, batch_lanes_blocks,
                                      batch_lanes_vmem_bytes,
                                      launch_vmem_bytes, strip_width)
from repro.core.scene import ConvScene, ceil_div
from repro.kernels import interpret_mode

Shape4 = Tuple[int, int, int, int]


def _in_index_map(scene: ConvScene):
    """Spatial index map shared by all three schedules.

    Returns ``at(oh, ow, i, j) -> (ih, iw)`` mapping output pixel (oh, ow)
    and filter tap (i, j) to the input block to fetch.  Dense route: the
    input was pre-padded, the dilated-tap coordinate indexes it directly.
    Sentinel route (lhs-dilated scenes): the coordinate is translated back
    through padding and dilation; holes and out-of-range taps resolve to
    the all-zero sentinel row/col appended at (inH, inW)."""
    dense = scene.dilH == 1 and scene.dilW == 1

    def at(oh, ow, i, j):
        ph = oh * scene.stdH + i * scene.fdilH
        pw = ow * scene.stdW + j * scene.fdilW
        if dense:
            return ph, pw
        qh = ph - scene.padH
        qw = pw - scene.padW
        ok = ((qh >= 0) & (qh % scene.dilH == 0)
              & (qh < scene.inH * scene.dilH)
              & (qw >= 0) & (qw % scene.dilW == 0)
              & (qw < scene.inW * scene.dilW))
        return (jnp.where(ok, qh // scene.dilH, scene.inH),
                jnp.where(ok, qw // scene.dilW, scene.inW))

    return at


# --------------------------------------------------------------------------
# launch geometry — one declarative spec per schedule
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class KernelGridSpec:
    """Declarative Pallas launch geometry for one schedule over one scene.

    Everything ``pl.pallas_call`` needs — grid extents, operand/output
    block shapes, index maps, dimension semantics, accumulator scratch —
    plus the structural facts the static verifier reasons over:
    ``reduction_dims`` (grid axes that revisit the same output block and
    must not move it) and ``reduction_extents`` (the sizes the kernel body
    compares ``program_id`` against to detect the first/last reduction
    step).  The index maps take grid coordinates in grid order and return
    *block* indices (Pallas convention: element offset = index * block),
    except ``in_index`` when ``in_elements``: then it returns the element
    offsets of the strip's input window (``pl.Element`` block dims).
    ``strip`` is the number of output columns one step computes (``bw``):
    the output block is ``strip`` columns wide and pixel ``p`` reads input
    window column ``p * scene.stdW``."""

    schedule: str
    scene: ConvScene
    grid: Tuple[int, ...]
    in_shape: Shape4            # operand shapes exactly as launched
    flt_shape: Shape4
    out_shape: Shape4
    in_block: Shape4
    flt_block: Shape4
    out_block: Shape4
    in_index: Callable[..., Tuple]
    flt_index: Callable[..., Tuple]
    out_index: Callable[..., Tuple]
    dimension_semantics: Tuple[str, ...]
    reduction_dims: Tuple[int, ...]
    reduction_extents: Tuple[int, ...]
    spatial_dims: Tuple[int, int]   # grid axes carrying (oh, ow)
    tap_dims: Tuple[int, int]       # grid axes carrying the (i, j) filter tap
    acc_shape: Tuple[int, ...]
    acc_dtype: Any = jnp.float32
    strip: int = 1

    @property
    def in_elements(self) -> bool:
        """The input window is addressed by element offset: the dense
        route of TB11/TB18 (the sentinel route and TB88 take blocks)."""
        return (self.schedule != "TB88" and self.scene.dilH == 1
                and self.scene.dilW == 1)

    @property
    def blocks(self) -> Tuple[int, int, int]:
        """(bm, bn, bk) as the footprint/cost model counts them."""
        bm = self.out_block[2]
        bn = self.out_block[3]
        bk = self.in_block[2]
        return bm, bn, bk


@dataclasses.dataclass(frozen=True)
class BatchLanesGridSpec:
    """Launch geometry of the batch-on-lanes weight gradient (WBL) over a
    *forward* scene, read by ``pl.pallas_call`` and by the static verifier
    alike (``analysis.verify.check_batch_lanes_spec``).

    Operands keep the forward's plan layout: IN ``[Hp, Wp, kp, Np]``
    spatially pre-padded with IC padded to ``kp`` (a sublane tile), dOUT
    ``[outH, outW, OC, Np]``; the output is dFLT as ``[taps*kp, OC]``, rows
    ordered (fh, fw, ic).  Every grid axis is a contraction: (lane block,
    dOUT row, strip of ``strip`` dOUT columns).  ``in_index`` returns the
    element offsets of the strip's input window; ``dout_index`` and
    ``out_index`` return block indices.  The body contracts, for strip
    pixel ``p``, window rows ``0..taps[0]-1`` and columns
    ``p*col_stride .. p*col_stride + taps[1]-1`` against dOUT column
    ``p``."""

    scene: ConvScene
    grid: Tuple[int, int, int]
    in_shape: Shape4
    dout_shape: Shape4
    out_shape: Tuple[int, int]
    in_block: Shape4
    dout_block: Shape4
    out_block: Tuple[int, int]
    in_index: Callable[..., Tuple]
    dout_index: Callable[..., Tuple]
    out_index: Callable[..., Tuple]
    taps: Tuple[int, int]
    col_stride: int
    strip: int
    acc_shape: Tuple[int, int]
    acc_dtype: Any = jnp.float32
    dimension_semantics: Tuple[str, ...] = ("arbitrary",) * 3


def batch_lanes_grid_spec(scene: ConvScene, *, in_shape: Shape4,
                          dout_shape: Shape4, bn: int, bw: int,
                          vmem_budget: int = 0) -> BatchLanesGridSpec:
    """The WBL launch over forward ``scene`` for operands launched at
    ``in_shape``/``dout_shape`` (``plan/build.launched_shapes``), ``bn``
    batch lanes and ``bw`` dOUT columns a step.  Validates divisibility
    and, when ``vmem_budget`` > 0, the ``analysis.footprint`` count."""
    kp, n = in_shape[2:]
    _require(dout_shape == (scene.outH, scene.outW, scene.OC, n),
             f"dOUT shape {dout_shape} does not match the output of "
             f"{scene.describe()} at {n} lanes")
    _require(n % bn == 0 and scene.outW % bw == 0,
             f"WBL blocking bn={bn}, bw={bw} must divide the launched "
             f"batch {n} and outW={scene.outW} of {scene.describe()}")
    (lead, _, _), _, (_, rows, _) = batch_lanes_blocks(scene, bn, bw)
    _require(kp * scene.fltH * scene.fltW == rows,
             f"input IC dim {kp} is not IC={scene.IC} padded to a sublane "
             f"tile for {scene.describe()}")
    sh, sw = scene.stdH, scene.stdW
    spec = BatchLanesGridSpec(
        scene=scene, grid=(n // bn, scene.outH, scene.outW // bw),
        in_shape=in_shape, dout_shape=dout_shape,
        out_shape=(rows, scene.OC),
        in_block=(scene.fltH, lead // scene.fltH, kp, bn),
        dout_block=(1, bw, scene.OC, bn), out_block=(rows, scene.OC),
        in_index=lambda nb, oh, st: (oh * sh, st * bw * sw, 0, nb * bn),
        dout_index=lambda nb, oh, st: (oh, st, 0, nb),
        out_index=lambda nb, oh, st: (0, 0),
        taps=(scene.fltH, scene.fltW), col_stride=sw, strip=bw,
        acc_shape=(rows, scene.OC))
    if vmem_budget > 0:
        need = batch_lanes_vmem_bytes(scene, bn, bw)
        _require(need <= vmem_budget,
                 f"WBL strip {bw} x {bn} lanes needs {need} B of VMEM "
                 f"(budget {vmem_budget} B) for {scene.describe()}")
    return spec


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise ValueError(msg)


def kernel_grid_spec(scene: ConvScene, schedule: str, *, in_shape: Shape4,
                     flt_shape: Shape4, bm: int = 0, bn: int = 0,
                     bk: int = 0, bw: int = 0,
                     vmem_budget: int = 0) -> KernelGridSpec:
    """Build the launch geometry for ``schedule`` over ``scene`` given the
    operand shapes exactly as they will be passed to the kernel (spatially
    pre-padded or sentinel-extended input, channel/batch-aligned dims — see
    ``plan/build._conv_body``).  ``bw`` is the TB11/TB18 strip width (0:
    ``footprint.strip_width``'s choice); TB88 and the sentinel route only
    take 1.

    Validates divisibility of the launched dims by the blocking and, when
    ``vmem_budget`` > 0, that the blocking's working set fits it (the same
    ``analysis.footprint`` arithmetic selection and tuning filter with) —
    raising ``ValueError`` instead of launching a kernel Mosaic cannot
    double-buffer."""
    fh, fw, k, m = flt_shape
    n = in_shape[-1]
    _require(in_shape[2] == k,
             f"input K dim {in_shape[2]} != filter K dim {k} for "
             f"{scene.describe()}")
    at = _in_index_map(scene)
    oh_ow = (scene.outH, scene.outW)
    dense = scene.dilH == 1 and scene.dilW == 1

    if schedule in ("TB11", "TB18"):
        if schedule == "TB11":
            bm = m
        bw = bw or strip_width(scene, schedule, bm, n, k)
        _require(bw == 1 or dense,
                 f"strip width {bw} on the sentinel route of "
                 f"{scene.describe()}: only 1 is contiguous")
        _require(scene.outW % bw == 0,
                 f"strip width {bw} must divide outW={scene.outW} for "
                 f"{scene.describe()}")
        # Dense: the strip's window by element offset, (bw-1)*stdW + 1
        # columns from the strip's first pixel's tap.  Sentinel (bw=1):
        # one column, block index = element offset.
        in_block = (1, (bw - 1) * scene.stdW + 1, k, n)

        def strip_in(oh, ow, i, j):
            return (*at(oh, ow * bw, i, j), 0, 0)

        strip_fields = dict(strip=bw, acc_shape=(bw, bm, n))
    else:
        _require(bw in (0, 1), f"{schedule} takes no strip (bw={bw})")

    if schedule == "TB11":
        spec = KernelGridSpec(
            schedule="TB11", scene=scene,
            grid=(scene.outH, scene.outW // bw, fh, fw),
            in_shape=in_shape, flt_shape=flt_shape,
            out_shape=(*oh_ow, m, n),
            in_block=in_block, flt_block=(fh, fw, k, m),
            out_block=(1, bw, m, n),
            in_index=strip_in,
            flt_index=lambda oh, ow, i, j: (0, 0, 0, 0),
            out_index=lambda oh, ow, i, j: (oh, ow, 0, 0),
            dimension_semantics=("parallel", "parallel",
                                 "arbitrary", "arbitrary"),
            reduction_dims=(2, 3), reduction_extents=(fh, fw),
            spatial_dims=(0, 1), tap_dims=(2, 3), **strip_fields)
    elif schedule == "TB18":
        _require(bm > 0 and m % bm == 0,
                 f"TB18 OC slice bm={bm} must divide the launched OC dim "
                 f"{m} for {scene.describe()}")
        spec = KernelGridSpec(
            schedule="TB18", scene=scene,
            grid=(m // bm, scene.outH, scene.outW // bw, fh, fw),
            in_shape=in_shape, flt_shape=flt_shape,
            out_shape=(*oh_ow, m, n),
            in_block=in_block, flt_block=(fh, fw, k, bm),
            out_block=(1, bw, bm, n),
            in_index=lambda mm, oh, ow, i, j: strip_in(oh, ow, i, j),
            flt_index=lambda mm, oh, ow, i, j: (0, 0, 0, mm),
            out_index=lambda mm, oh, ow, i, j: (oh, ow, mm, 0),
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary", "arbitrary"),
            reduction_dims=(3, 4), reduction_extents=(fh, fw),
            spatial_dims=(1, 2), tap_dims=(3, 4), **strip_fields)
    elif schedule == "TB88":
        _require(bm > 0 and bn > 0 and bk > 0
                 and m % bm == 0 and n % bn == 0 and k % bk == 0,
                 f"TB88 blocking ({bm}/{bn}/{bk}) must divide the launched "
                 f"(M={m}, N={n}, K={k}) dims for {scene.describe()}")
        nk = k // bk
        spec = KernelGridSpec(
            schedule="TB88", scene=scene,
            grid=(*oh_ow, m // bm, n // bn, fh, fw, nk),
            in_shape=in_shape, flt_shape=flt_shape,
            out_shape=(*oh_ow, m, n),
            in_block=(1, 1, bk, bn), flt_block=(1, 1, bk, bm),
            out_block=(1, 1, bm, bn),
            in_index=lambda oh, ow, mm, nn, i, j, kk: (
                *at(oh, ow, i, j), kk, nn),
            flt_index=lambda oh, ow, mm, nn, i, j, kk: (i, j, kk, mm),
            out_index=lambda oh, ow, mm, nn, i, j, kk: (oh, ow, mm, nn),
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "parallel", "arbitrary", "arbitrary",
                                 "arbitrary"),
            reduction_dims=(4, 5, 6), reduction_extents=(fh, fw, nk),
            spatial_dims=(0, 1), tap_dims=(4, 5),
            acc_shape=(bm, bn))
    else:
        raise ValueError(f"unknown schedule {schedule!r}")

    if vmem_budget > 0:
        need = launch_vmem_bytes(scene, schedule, *spec.blocks,
                                 spec.strip)
        _require(need <= vmem_budget,
                 f"{schedule} blocking {spec.blocks} x strip {spec.strip} "
                 f"needs {need} B of VMEM (budget {vmem_budget} B) for "
                 f"{scene.describe()}")
    return spec


def _launch(spec: KernelGridSpec, inp: jax.Array, flt: jax.Array, *,
            interpret: bool) -> jax.Array:
    """One ``pl.pallas_call`` from a ``KernelGridSpec`` — the only place
    the three schedules turn geometry into a launch."""
    if spec.schedule == "TB88":
        kernel = functools.partial(_tb88_kernel,
                                   red_dims=spec.reduction_extents,
                                   out_dtype=inp.dtype)
    else:
        kernel = functools.partial(_strip_kernel, tap_dims=spec.tap_dims,
                                   flt_hw=spec.reduction_extents,
                                   col_stride=spec.scene.stdW,
                                   out_dtype=inp.dtype)
    in_block = spec.in_block
    if spec.in_elements:  # every dim an Element, as Mosaic requires
        in_block = tuple(pl.Element(d) for d in in_block)
    return pl.pallas_call(
        kernel,
        grid=spec.grid,
        in_specs=[
            pl.BlockSpec(in_block, spec.in_index),
            pl.BlockSpec(spec.flt_block, spec.flt_index),
        ],
        out_specs=pl.BlockSpec(spec.out_block, spec.out_index),
        out_shape=jax.ShapeDtypeStruct(spec.out_shape, inp.dtype),
        scratch_shapes=[pltpu.VMEM(spec.acc_shape, spec.acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=spec.dimension_semantics),
        interpret=interpret,
    )(inp, flt)


def _dot_kt(flt_blk: jax.Array, in_blk: jax.Array) -> jax.Array:
    """(K, M) x (K, N) -> (M, N) contracting K (the paper's MM_unit, Eq. 2).

    FLT is consumed in its natural [.., IC, OC] layout: no transposition, the
    TPU analogue of the paper's `ldde`-broadcast trick (§4.4.1).  f32
    operands contract at f32 precision on the MXU whatever Mosaic's default
    or an enclosing ``jax.default_matmul_precision``: an f32 scene is an f32
    convolution."""
    f32 = flt_blk.dtype == jnp.float32 and in_blk.dtype == jnp.float32
    return jax.lax.dot_general(
        flt_blk, in_blk,
        dimension_numbers=(((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST if f32 else None,
        preferred_element_type=jnp.float32,
    )


# --------------------------------------------------------------------------
# TB11 (whole-FLT residency) and TB18 (OC-sliced FLT residency): one body
# --------------------------------------------------------------------------
def _strip_kernel(in_ref, flt_ref, out_ref, acc_ref, *,
                  tap_dims: Tuple[int, int], flt_hw: Tuple[int, int],
                  col_stride: int, out_dtype):
    """One (strip, tap) step: pixel ``p`` of the strip contracts input
    window column ``p * col_stride`` against the tap's (K, bm) filter
    slice into its own accumulator tile."""
    fh = pl.program_id(tap_dims[0])
    fw = pl.program_id(tap_dims[1])
    first = jnp.logical_and(fh == 0, fw == 0)
    last = jnp.logical_and(fh == flt_hw[0] - 1, fw == flt_hw[1] - 1)

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    flt_blk = flt_ref[fh, fw]          # (K, bm) dynamic-sliced from resident FLT
    # Unrolled on purpose: as a rolled fori_loop the strip ran VGG-16 at
    # B=128 2.6x slower on a TPU v5e, and Mosaic takes no partial unroll.
    for p in range(acc_ref.shape[0]):
        acc_ref[p] += _dot_kt(flt_blk, in_ref[0, p * col_stride])

    @pl.when(last)
    def _store():
        out_ref[0] = acc_ref[...].astype(out_dtype)


def conv_tb11(inp: jax.Array, flt: jax.Array, scene: ConvScene, *,
              bw: int = 0, interpret: Optional[bool] = None) -> jax.Array:
    """inp pre-padded (or compact+sentinel when lhs-dilated, see module doc);
    returns [outH, outW, M, N].  ``bw`` is the strip width (0: the
    footprint's choice).  ``interpret`` None derives the kernel mode from
    the platform (``repro.kernels.interpret_mode``); tests pass False to
    compile for a described chip."""
    spec = kernel_grid_spec(scene, "TB11", in_shape=inp.shape,
                            flt_shape=flt.shape, bw=bw,
                            vmem_budget=VMEM_BUDGET)
    return _launch(spec, inp, flt, interpret=interpret_mode(interpret))


def conv_tb18(inp: jax.Array, flt: jax.Array, scene: ConvScene, *, bm: int,
              bw: int = 0, interpret: Optional[bool] = None) -> jax.Array:
    spec = kernel_grid_spec(scene, "TB18", in_shape=inp.shape,
                            flt_shape=flt.shape, bm=bm, bw=bw,
                            vmem_budget=VMEM_BUDGET)
    return _launch(spec, inp, flt, interpret=interpret_mode(interpret))


# --------------------------------------------------------------------------
# TB88: fully tiled GEMM per output pixel
# --------------------------------------------------------------------------
def _tb88_kernel(in_ref, flt_ref, out_ref, acc_ref, *, red_dims, out_dtype):
    fh = pl.program_id(4)
    fw = pl.program_id(5)
    kk = pl.program_id(6)
    nfh, nfw, nk = red_dims
    first = (fh == 0) & (fw == 0) & (kk == 0)
    last = (fh == nfh - 1) & (fw == nfw - 1) & (kk == nk - 1)

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _dot_kt(flt_ref[0, 0], in_ref[0, 0])

    @pl.when(last)
    def _store():
        out_ref[0, 0] = acc_ref[...].astype(out_dtype)


def conv_tb88(inp: jax.Array, flt: jax.Array, scene: ConvScene, *, bm: int,
              bn: int, bk: int, interpret: Optional[bool] = None
              ) -> jax.Array:
    spec = kernel_grid_spec(scene, "TB88", in_shape=inp.shape,
                            flt_shape=flt.shape, bm=bm, bn=bn, bk=bk,
                            vmem_budget=VMEM_BUDGET)
    return _launch(spec, inp, flt, interpret=interpret_mode(interpret))


# --------------------------------------------------------------------------
# WBL: the batch-on-lanes weight gradient
# --------------------------------------------------------------------------
def _dot_nt(in_blk: jax.Array, dout_blk: jax.Array) -> jax.Array:
    """(R, N) x (M, N) -> (R, M) contracting the lane dim N (the batch):
    the stationary dOUT tile serves every tap row of ``in_blk``."""
    f32 = in_blk.dtype == jnp.float32 and dout_blk.dtype == jnp.float32
    return jax.lax.dot_general(
        in_blk, dout_blk,
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST if f32 else None,
        preferred_element_type=jnp.float32,
    )


def _batch_lanes_kernel(in_ref, dout_ref, out_ref, acc_ref, *,
                        taps: Tuple[int, int], col_stride: int, out_dtype):
    """One (lane block, dOUT row, strip) step: pixel ``p`` stacks its
    taps' (kp, bn) input slabs into one (taps*kp, bn) operand and
    contracts it with dOUT column ``p`` over the batch lanes."""
    steps = [(pl.program_id(d), pl.num_programs(d)) for d in range(3)]
    first = functools.reduce(jnp.logical_and, [i == 0 for i, _ in steps])
    last = functools.reduce(jnp.logical_and,
                            [i == e - 1 for i, e in steps])
    fh, fw = taps
    kp, bn = in_ref.shape[2:]

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Rolled: each pixel is one (taps*kp, bn) x (bn, OC) dot, large enough
    # that the loop costs little, and a wide strip compiles quickly.
    def pixel(p, carry):
        win = in_ref[pl.ds(0, fh), pl.ds(p * col_stride, fw)]
        acc_ref[...] += _dot_nt(win.reshape(fh * fw * kp, bn),
                                dout_ref[0, p])
        return carry

    jax.lax.fori_loop(0, dout_ref.shape[1], pixel, 0)

    @pl.when(last)
    def _store():
        out_ref[...] = acc_ref[...].astype(out_dtype)


def _launch_batch_lanes(spec: BatchLanesGridSpec, inp: jax.Array,
                        d_out: jax.Array, *, interpret: bool) -> jax.Array:
    kernel = functools.partial(_batch_lanes_kernel, taps=spec.taps,
                               col_stride=spec.col_stride,
                               out_dtype=inp.dtype)
    return pl.pallas_call(
        kernel,
        grid=spec.grid,
        in_specs=[
            pl.BlockSpec(tuple(pl.Element(d) for d in spec.in_block),
                         spec.in_index),
            pl.BlockSpec(spec.dout_block, spec.dout_index),
        ],
        out_specs=pl.BlockSpec(spec.out_block, spec.out_index),
        out_shape=jax.ShapeDtypeStruct(spec.out_shape, inp.dtype),
        scratch_shapes=[pltpu.VMEM(spec.acc_shape, spec.acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=spec.dimension_semantics),
        interpret=interpret,
    )(inp, d_out)


def wgrad_batch_lanes(inp: jax.Array, d_out: jax.Array, scene: ConvScene,
                      *, bn: int, bw: int,
                      interpret: Optional[bool] = None) -> jax.Array:
    """dFLT of forward ``scene`` as ``[fltH*fltW*kp, OC]`` from IN
    ``[Hp, Wp, kp, Np]`` (spatially pre-padded, IC zero-padded to ``kp``)
    and dOUT ``[outH, outW, OC, Np]``: the sum over (oh, ow, b) of
    IN[oh*stdH + fh, ow*stdW + fw, ic, b] * dOUT[oh, ow, oc, b]."""
    spec = batch_lanes_grid_spec(scene, in_shape=inp.shape,
                                 dout_shape=d_out.shape, bn=bn, bw=bw,
                                 vmem_budget=VMEM_BUDGET)
    return _launch_batch_lanes(spec, inp, d_out,
                               interpret=interpret_mode(interpret))
