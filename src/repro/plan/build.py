"""Plan construction — schedule resolution, backward-scene derivation, and
padded-shape precomputation, all performed exactly once per plan.

The plan-once / execute-many contract (cuDNN's find-then-execute descriptor
model, on MG3M terms):

  * ``make_plan(scene, op, policy=...)`` runs the multi-grained selector
    once (``policy``: analytic roofline, tuned-cache resolution, or a forced
    grain), derives every padded/aligned shape and slice extent into a
    frozen ``ExecSpec``, and — for the backward ops — derives the backward
    convolution's own ``ConvScene`` so dgrad and wgrad go through the same
    selector as fprop;
  * ``ConvPlan.execute(a, b)`` dispatches straight into the Pallas kernels
    with the precomputed spec: zero schedule resolutions, zero tune-cache
    IO, zero shape arithmetic per call.

Backward ops as scenes (the selector owns all three directions — strided
forwards included, via the scene's dilation axes):

  DGRAD  dIN = conv(dOUT, rot180(FLT) with IC/OC swapped) — a fresh scene
         with B'=B, IC'=OC, OC'=IC over dOUT's spatial dims.  A strided
         forward's adjoint is the same conv with dOUT *lhs-dilated* by the
         stride (``dilH/dilW`` on the dgrad scene; stride and lhs dilation
         swap roles between a conv and its input-adjoint), plus ``apad``
         extra high-side zeros when the forward had a stride remainder.
         The kernels read the compact dOUT through hole-skipping index
         maps — no zero-interleaved scatter is materialized.
  WGRAD  dFLT[fh,fw,ic,oc] = sum_{oh,ow,b} IN[std*oh+fh, std*ow+fw, ic, b]
         * dOUT[oh,ow,oc,b] *is* a convolution with the batch dim
         contracted: input IN with (B, IC) swapped, filter dOUT with
         (B, OC) swapped, scene B'=IC, IC'=B, OC'=OC, filter spatial
         outHxoutW.  A strided forward *rhs-dilates* the taps
         (``fdilH/fdilW`` on the wgrad scene); a stride remainder grows
         the conv's spatial output past fltHxfltW, sliced back by the
         executor (``ExecSpec.out_h/out_w``).
         Forward scenes with IC under a sublane tile (image-input stems)
         take the WBL route instead under the analytic and tuned policies
         (``mapping.batch_lanes_choice``): the swap would put IC on the
         lanes, so WBL keeps the forward layout, contracts the batch on
         the lanes and executes over the forward scene itself.

  The only genuinely inexpressible adjoint left is padding exceeding the
  dilated filter extent minus one (the adjoint's padding would be
  negative): that dgrad — and only that op — records
  ``uses_reference=True`` and executes the exact jnp adjoint, while fprop
  and wgrad of the same scene still dispatch to Pallas.
"""
from __future__ import annotations

import dataclasses
import enum
import functools
import time
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.analysis.footprint import batch_lanes_strip, strip_width
from repro.core.mapping import (BATCH_LANES, ScheduleChoice,
                                batch_lanes_choice, select_schedule)
from repro.core.scene import ConvScene, round_up
from repro.kernels import mg3m_conv as kernels
from repro.kernels import ref
from repro.obs.metrics import default_metrics
from repro.obs.trace import default_tracer

PolicySpec = Union[None, str, ScheduleChoice]


class ConvOp(enum.Enum):
    """The three convolution directions a plan can execute."""

    FPROP = "fprop"   # execute(inp, flt)   -> out
    DGRAD = "dgrad"   # execute(d_out, flt) -> d_in
    WGRAD = "wgrad"   # execute(inp, d_out) -> d_flt


# --------------------------------------------------------------------------
# policy resolution (once per plan)
# --------------------------------------------------------------------------
def _active_cost_model():
    """Calibrated cost model when an artifact (or explicitly-installed model)
    exists, else None = analytic default.  Silent fallback — selection must
    work without the tune subsystem."""
    try:
        from repro.tune.calibrate import active_cost_model  # avoids cycle
        return active_cost_model()
    except Exception:  # noqa: BLE001 — any tune-side failure = analytic model
        return None


def policy_tag(policy: PolicySpec) -> str:
    """Canonical policy label (registry keys, plan metadata).  Idempotent:
    an already-canonical tag (e.g. a plan's own ``.policy``) maps to itself."""
    if isinstance(policy, ScheduleChoice):
        return (f"forced:{policy.schedule}"
                f"@{policy.bm}/{policy.bn}/{policy.bk}")
    if policy in (None, "analytic"):
        return "analytic"
    if policy in ("auto", "tuned"):
        return "tuned"
    if isinstance(policy, str) and policy.startswith("forced:"):
        return policy
    return f"forced:{policy}"


def resolve_policy(scene: ConvScene, policy: PolicySpec) -> ScheduleChoice:
    """One-time schedule resolution for a plan.

      None / "analytic"   multi-grained selection under the active cost model
                          (calibrated when an artifact exists, else roofline);
      "auto" / "tuned"    tuned-cache lookup first, cost-model selection on
                          miss — never measures (see repro.tune);
      "TB11"/"TB18"/"TB88"  forced schedule, model-chosen blocks; raises if
                          the forced grain cannot fit VMEM;
      ScheduleChoice      used exactly as given (the tuner's measurement path).
    """
    if isinstance(policy, ScheduleChoice):
        return policy
    m = default_metrics()
    m.counter("repro.plan.resolutions").inc()
    t0 = time.perf_counter()
    try:
        if policy in ("auto", "tuned"):
            from repro.tune.autotune import resolve_schedule  # avoids cycle
            return resolve_schedule(scene)
        if policy in (None, "analytic"):
            return select_schedule(scene, model=_active_cost_model())
        return select_schedule(scene, allowed=(policy,),
                               model=_active_cost_model())
    finally:
        m.histogram("repro.plan.resolve_s").observe(time.perf_counter() - t0)


# --------------------------------------------------------------------------
# padded/aligned shape derivation (once per plan)
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ExecSpec:
    """Everything ``execute`` needs, precomputed: clipped blocks, spatial
    pre-padding (or the sentinel route for lhs-dilated scenes), channel/
    batch alignment targets, slice-back extents."""

    schedule: str
    bm: int                # clipped blocks actually passed to the kernel
    bn: int
    bk: int
    pad_h: int             # spatial pre-padding (scene padH/padW)
    pad_w: int
    mp: int                # aligned OC target (flt minor-dim padding)
    np_: int               # aligned B target (in minor-dim padding)
    kp: int                # aligned IC target (reduction-dim padding)
    m: int                 # slice-back extents of the true output
    n: int
    apad_h: int = 0        # extra high-side spatial pre-padding
    apad_w: int = 0
    sentinel: bool = False  # lhs-dilated: compact input + zero sentinel
    out_h: int = 0         # spatial slice-back extents (0 = full output;
    out_w: int = 0         # wgrad trims stride-remainder rows/cols)
    bw: int = 1            # output columns per grid step (TB11/TB18 strip,
                           # WBL: dOUT columns)


def derive_exec_spec(scene: ConvScene, choice: ScheduleChoice,
                     out_hw: Optional[Tuple[int, int]] = None) -> ExecSpec:
    """Precompute every padded/aligned dim the kernel dispatch needs —
    once per plan.
    ``out_hw`` overrides the spatial slice-back extents (the wgrad scene's
    conv output can exceed the true dFLT spatial dims by the forward's
    stride remainder)."""
    m, n, k = scene.M, scene.N, scene.K
    if choice.schedule == BATCH_LANES:   # scene is the forward scene
        bn = choice.bn
        return ExecSpec(BATCH_LANES, m, bn, choice.bk, scene.padH,
                        scene.padW, m, round_up(n, bn), choice.bk, m, n,
                        bw=batch_lanes_strip(scene, bn))
    oh, ow = out_hw if out_hw is not None else (scene.outH, scene.outW)
    extra = dict(apad_h=scene.apadH, apad_w=scene.apadW,
                 sentinel=scene.dilH > 1 or scene.dilW > 1,
                 out_h=oh, out_w=ow)
    if choice.schedule == "TB11":
        return ExecSpec("TB11", m, n, k, scene.padH, scene.padW, m, n, k,
                        m, n, bw=strip_width(scene, "TB11", m, n, k),
                        **extra)
    if choice.schedule == "TB18":
        bm = min(choice.bm, m)
        return ExecSpec("TB18", bm, n, k, scene.padH, scene.padW,
                        round_up(m, bm), n, k, m, n,
                        bw=strip_width(scene, "TB18", bm, n, k), **extra)
    bm, bn, bk = min(choice.bm, m), min(choice.bn, n), min(choice.bk, k)
    return ExecSpec("TB88", bm, bn, bk, scene.padH, scene.padW,
                    round_up(m, bm), round_up(n, bn), round_up(k, bk),
                    m, n, **extra)


def launched_shapes(scene: ConvScene, spec: ExecSpec
                    ) -> Tuple[Tuple[int, int, int, int],
                               Tuple[int, int, int, int]]:
    """(input, filter) shapes exactly as ``_conv_body`` launches them:
    spatial pre-padding (or the +1 sentinel row/col), channel/batch
    alignment per schedule.  The static verifier rebuilds the
    ``KernelGridSpec`` from these, so what it proves is what executes."""
    if spec.sentinel:
        ih, iw = scene.inH + 1, scene.inW + 1
    else:
        ih = scene.inH + 2 * spec.pad_h + spec.apad_h
        iw = scene.inW + 2 * spec.pad_w + spec.apad_w
    if spec.schedule == BATCH_LANES:     # (IN, dOUT) of the forward scene
        return ((ih, iw, spec.kp, spec.np_),
                (scene.outH, scene.outW, scene.OC, spec.np_))
    if spec.schedule == "TB11":
        return ((ih, iw, scene.K, scene.N),
                (scene.fltH, scene.fltW, scene.K, scene.M))
    if spec.schedule == "TB18":
        return ((ih, iw, scene.K, scene.N),
                (scene.fltH, scene.fltW, scene.K, spec.mp))
    return ((ih, iw, spec.kp, spec.np_),
            (scene.fltH, scene.fltW, spec.kp, spec.mp))


# --------------------------------------------------------------------------
# backward-scene derivation
# --------------------------------------------------------------------------
def _stride_remainders(scene: ConvScene) -> Tuple[int, int]:
    """Spatial slack the forward's floor-div discards: input rows/cols past
    the last window position.  The adjoint must re-grow them (as zeros of
    gradient) via extra high-side padding."""
    rh = (scene.dilated_inH + 2 * scene.padH
          - scene.dilated_fltH) % scene.stdH
    rw = (scene.dilated_inW + 2 * scene.padW
          - scene.dilated_fltW) % scene.stdW
    return rh, rw


def grad_input_scene(scene: ConvScene) -> ConvScene:
    """The dIN convolution's scene: conv of dOUT with the rotated,
    IC/OC-swapped filter.  Stride and lhs dilation swap roles between a
    conv and its input-adjoint: a strided forward yields a *lhs-dilated*
    dgrad scene (dOUT read with stride-many holes between elements), a
    lhs-dilated forward yields a *strided* one; filter dilation carries
    over unchanged.  Raises ``ValueError`` for the genuinely inexpressible
    case — padding exceeding the dilated filter extent minus one."""
    why = _dgrad_blocker(scene)
    if why:
        raise ValueError(f"dgrad of {scene.describe()} has no MG3M scene: {why}")
    rh, rw = _stride_remainders(scene)
    return ConvScene(
        B=scene.B, IC=scene.OC, OC=scene.IC,
        inH=scene.outH, inW=scene.outW,
        fltH=scene.fltH, fltW=scene.fltW,
        padH=scene.dilated_fltH - 1 - scene.padH,
        padW=scene.dilated_fltW - 1 - scene.padW,
        stdH=scene.dilH, stdW=scene.dilW,
        dilH=scene.stdH, dilW=scene.stdW,
        fdilH=scene.fdilH, fdilW=scene.fdilW,
        apadH=rh, apadW=rw, dtype=scene.dtype)


def grad_filter_scene(scene: ConvScene) -> ConvScene:
    """The dFLT convolution's scene: batch-contracted conv with filter
    spatial = outHxoutW.  A strided forward *rhs-dilates* the taps (the
    dOUT-as-filter is read ``std`` apart); a rhs-dilated forward makes the
    wgrad conv strided.  The conv's spatial output is fltHxfltW plus the
    forward's stride remainder — the executor slices it back."""
    why = _wgrad_blocker(scene)
    if why:
        raise ValueError(f"wgrad of {scene.describe()} has no MG3M scene: {why}")
    return ConvScene(
        B=scene.IC, IC=scene.B, OC=scene.OC,
        inH=scene.inH, inW=scene.inW,
        fltH=scene.outH, fltW=scene.outW,
        padH=scene.padH, padW=scene.padW,
        stdH=scene.fdilH, stdW=scene.fdilW,
        dilH=scene.dilH, dilW=scene.dilW,
        fdilH=scene.stdH, fdilW=scene.stdW,
        dtype=scene.dtype)


def _dgrad_blocker(scene: ConvScene) -> Optional[str]:
    if scene.apadH or scene.apadW:
        return ("asymmetric extra padding: the adjoint of an apad scene "
                "is not itself an MG3M scene")
    if (scene.padH > scene.dilated_fltH - 1
            or scene.padW > scene.dilated_fltW - 1):
        return ("padding exceeds dilated-filter-extent-1: adjoint padding "
                "would be negative")
    return None


def _wgrad_blocker(scene: ConvScene) -> Optional[str]:
    if scene.apadH or scene.apadW:
        return ("asymmetric extra padding: the weight-gradient of an apad "
                "scene is not itself an MG3M scene")
    return None


# --------------------------------------------------------------------------
# executors — jitted on the frozen (scene, spec); no per-call derivation
# --------------------------------------------------------------------------
def _pad_axis(x: jax.Array, axis: int, to: int) -> jax.Array:
    cur = x.shape[axis]
    if cur == to:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, to - cur)
    return jnp.pad(x, pads)


# Operand transforms that turn each backward op into an fprop-shaped conv
# over its exec scene.  One definition: the in-process executors below and
# the mesh-sharded wrapper (repro.shard.plan) must agree byte-for-byte on
# how dgrad/wgrad operands map onto the exec scene's (inp, flt) slots.
def dgrad_operands(d_out: jax.Array, flt: jax.Array
                   ) -> Tuple[jax.Array, jax.Array]:
    """(inp, flt) of the dgrad exec conv: dOUT against the rot180'd,
    IC/OC-swapped filter."""
    return d_out, jnp.flip(flt, axis=(0, 1)).swapaxes(2, 3)


def wgrad_operands(inp: jax.Array, d_out: jax.Array
                   ) -> Tuple[jax.Array, jax.Array]:
    """(inp, flt) of the wgrad exec conv: IN with (IC, B) swapped against
    dOUT with (OC, B) swapped."""
    return inp.swapaxes(2, 3), d_out.swapaxes(2, 3)


def wgrad_finish(out: jax.Array) -> jax.Array:
    """Wgrad exec-conv output -> FLT layout (the spatial slice-back to
    fltH x fltW happens before this, via ``ExecSpec.out_h/out_w`` or the
    sharded wrapper's explicit slice)."""
    return out.transpose(0, 1, 3, 2)


def _conv_body(inp: jax.Array, flt: jax.Array, scene: ConvScene,
               spec: ExecSpec) -> jax.Array:
    """Kernel dispatch from a precomputed spec (no shape arithmetic here).

    Lhs-dilated scenes take the sentinel route: the compact input gains one
    trailing zero row/col and the kernel's index maps resolve padding,
    holes, and out-of-range taps onto it — no zero-interleaved buffer."""
    if spec.sentinel:
        inp_p = jnp.pad(inp, ((0, 1), (0, 1), (0, 0), (0, 0)))
    else:
        inp_p = jnp.pad(inp, ((spec.pad_h, spec.pad_h + spec.apad_h),
                              (spec.pad_w, spec.pad_w + spec.apad_w),
                              (0, 0), (0, 0)))
    if spec.schedule == "TB11":
        out = kernels.conv_tb11(inp_p, flt, scene, bw=spec.bw)
    elif spec.schedule == "TB18":
        flt_a = _pad_axis(flt, 3, spec.mp)
        out = kernels.conv_tb18(inp_p, flt_a, scene, bm=spec.bm,
                                bw=spec.bw)[:, :, :spec.m, :]
    else:
        inp_a = _pad_axis(_pad_axis(inp_p, 2, spec.kp), 3, spec.np_)
        flt_a = _pad_axis(_pad_axis(flt, 2, spec.kp), 3, spec.mp)
        out = kernels.conv_tb88(inp_a, flt_a, scene, bm=spec.bm, bn=spec.bn,
                                bk=spec.bk)[:, :, :spec.m, :spec.n]
    if (spec.out_h, spec.out_w) not in ((0, 0), (scene.outH, scene.outW)):
        out = out[:spec.out_h, :spec.out_w]
    return out


@functools.partial(jax.jit, static_argnames=("scene", "spec"))
def _exec_fprop(inp, flt, scene: ConvScene, spec: ExecSpec):
    return _conv_body(inp, flt, scene, spec)


@functools.partial(jax.jit, static_argnames=("scene", "spec"))
def _exec_dgrad(d_out, flt, scene: ConvScene, spec: ExecSpec):
    # scene/spec here describe the *dgrad* scene (grad_input_scene); for a
    # strided forward it is lhs-dilated and the kernels read the compact
    # dOUT through the sentinel index maps.
    a, b = dgrad_operands(d_out, flt)   # rot180 + IC<->OC
    return _conv_body(a, b, scene, spec)


def _batch_lanes_body(inp: jax.Array, d_out: jax.Array, scene: ConvScene,
                      spec: ExecSpec) -> jax.Array:
    """WBL dispatch over the forward scene: one pad of IN (spatial, IC to
    a sublane tile, batch to the lane blocks), the kernel, and the
    ``[taps*kp, OC]`` result viewed as FLT with the IC padding dropped."""
    inp_p = jnp.pad(inp, ((spec.pad_h, spec.pad_h), (spec.pad_w, spec.pad_w),
                          (0, spec.kp - scene.IC), (0, spec.np_ - scene.B)))
    out = kernels.wgrad_batch_lanes(inp_p, _pad_axis(d_out, 3, spec.np_),
                                    scene, bn=spec.bn, bw=spec.bw)
    return out.reshape(scene.fltH, scene.fltW, spec.kp,
                       scene.OC)[:, :, :scene.IC]


@functools.partial(jax.jit, static_argnames=("scene", "spec"))
def _exec_wgrad(inp, d_out, scene: ConvScene, spec: ExecSpec):
    # scene/spec describe the *wgrad* scene (grad_filter_scene): input with
    # (IC, B) swapped, filter = dOUT with (OC, B) swapped (rhs-dilated by
    # the forward stride), output [fltH(+r), fltW(+r), OC, IC] sliced back
    # to the true filter dims (spec.out_h/out_w, inside _conv_body) and
    # transposed to the FLT layout.  The WBL route's scene is the forward
    # scene and takes the operands as they come.
    if spec.schedule == BATCH_LANES:
        return _batch_lanes_body(inp, d_out, scene, spec)
    a, b = wgrad_operands(inp, d_out)
    return wgrad_finish(_conv_body(a, b, scene, spec))


# Reference executors (the recorded fallbacks).
@functools.partial(jax.jit, static_argnames=("scene",))
def _ref_fprop(inp, flt, scene: ConvScene):
    return ref.conv_ref(inp, flt, scene)


@functools.partial(jax.jit, static_argnames=("scene",))
def _ref_dgrad(d_out, flt, scene: ConvScene):
    """Exact adjoint via jax.vjp of the reference conv — conv is linear in
    IN, so the primal point is irrelevant (zeros)."""
    zero = jnp.zeros(scene.in_shape(), d_out.dtype)
    _, vjp = jax.vjp(lambda i: ref.conv_ref(i, flt, scene), zero)
    return vjp(d_out)[0]


@functools.partial(jax.jit, static_argnames=("scene",))
def _ref_wgrad(inp, d_out, scene: ConvScene):
    """Exact dL/dFLT via jax.vjp of the reference conv — linear in FLT, so
    the primal point is irrelevant (zeros); fp32 accumulation inside
    ``conv_ref``.  Covers every scene the oracle covers (stride, both
    dilation axes, asymmetric padding)."""
    zero = jnp.zeros(scene.flt_shape(), d_out.dtype)
    _, vjp = jax.vjp(lambda f: ref.conv_ref(inp, f, scene), zero)
    return vjp(d_out)[0]


# --------------------------------------------------------------------------
# the plan
# --------------------------------------------------------------------------
# (arg-a shape, arg-b shape, result shape) accessors per op, on the fwd scene
_IO_SHAPES = {
    ConvOp.FPROP: ("in_shape", "flt_shape", "out_shape"),
    ConvOp.DGRAD: ("out_shape", "flt_shape", "in_shape"),
    ConvOp.WGRAD: ("in_shape", "out_shape", "flt_shape"),
}


@dataclasses.dataclass(frozen=True)
class ConvPlan:
    """Frozen, executable convolution plan for one (scene, op, policy).

    All selection and shape work happened in ``make_plan``; ``execute`` is a
    pure dispatch into a jitted kernel call.  ``uses_reference`` + ``notes``
    surface when the plan bypasses Pallas (the recorded dgrad/wgrad
    fallbacks) — metadata, not buried comments.
    """

    scene: ConvScene                    # the *forward* scene the plan serves
    op: ConvOp
    policy: str                         # canonical tag (see ``policy_tag``)
    uses_reference: bool
    notes: Tuple[str, ...] = ()
    exec_scene: Optional[ConvScene] = None   # scene actually dispatched
    choice: Optional[ScheduleChoice] = None  # None on reference plans
    spec: Optional[ExecSpec] = None

    # -- execution ---------------------------------------------------------
    def execute(self, a: jax.Array, b: jax.Array) -> jax.Array:
        """Run the planned op: (inp, flt) for FPROP, (d_out, flt) for DGRAD,
        (inp, d_out) for WGRAD.  Enqueues the kernel under a
        ``repro.plan.execute`` span (args: the op and, on a Pallas plan,
        ``schedule`` and ``strip``, the output columns per grid step) and
        returns without waiting for it."""
        a_shape, b_shape, _ = self.io_shapes()
        if a.shape != a_shape or b.shape != b_shape:
            raise ValueError(
                f"{self.op.value} plan for {self.scene.describe()} expects "
                f"operands {a_shape} x {b_shape}, got {a.shape} x {b.shape}")
        with default_tracer().span("repro.plan.execute") as sp:
            if sp:
                sp.set(op=self.op.value)
                if self.spec is not None:
                    sp.set(schedule=self.spec.schedule, strip=self.spec.bw)
            if self.uses_reference:
                fn = {ConvOp.FPROP: _ref_fprop, ConvOp.DGRAD: _ref_dgrad,
                      ConvOp.WGRAD: _ref_wgrad}[self.op]
                return fn(a, b, self.scene)
            fn = {ConvOp.FPROP: _exec_fprop, ConvOp.DGRAD: _exec_dgrad,
                  ConvOp.WGRAD: _exec_wgrad}[self.op]
            return fn(a, b, self.exec_scene, self.spec)

    __call__ = execute

    # -- introspection -----------------------------------------------------
    def io_shapes(self) -> Tuple[Tuple[int, ...], Tuple[int, ...],
                                 Tuple[int, ...]]:
        """(arg-a shape, arg-b shape, result shape) of ``execute``."""
        names = _IO_SHAPES[self.op]
        return tuple(getattr(self.scene, nm)() for nm in names)

    @property
    def schedule(self) -> Optional[str]:
        return self.choice.schedule if self.choice else None

    @property
    def predicted_s(self) -> Optional[float]:
        """Modeled whole-dispatch runtime (None on reference plans).  The
        uniform accessor shared with ``ShardedConvPlan``, whose prediction
        additionally carries the collective term."""
        return self.choice.predicted_s if self.choice else None

    @property
    def shard_tag(self) -> Optional[str]:
        """Partition fragment of this plan's registry signature — always
        None for an in-process plan (see ``repro.shard`` for the mesh-aware
        counterpart)."""
        return None

    def describe(self) -> str:
        how = ("jnp-reference" if self.uses_reference else
               f"{self.choice.schedule}"
               f"({self.spec.bm}/{self.spec.bn}/{self.spec.bk} "
               f"strip={self.spec.bw})")
        return (f"plan({self.op.value} {how} policy={self.policy} "
                f"{self.scene.describe()})")


def make_plan(scene: ConvScene, op: Union[ConvOp, str] = ConvOp.FPROP, *,
              policy: PolicySpec = "analytic") -> ConvPlan:
    """Build a frozen ``ConvPlan``: resolve the schedule once, derive the
    backward scene (DGRAD/WGRAD), precompute every padded/aligned shape.

    ``policy``: "analytic" (roofline/calibrated selection), "tuned"
    (schedule-cache resolution, analytic on miss), a forced "TB11"/"TB18"/
    "TB88", or an exact ``ScheduleChoice``.  The legacy spellings ``None``
    and ``"auto"`` alias "analytic" and "tuned".  A WGRAD plan whose
    forward scene qualifies (``mapping.batch_lanes_choice``) takes the WBL
    route under "analytic" and "tuned", or pinned by a WBL choice; a
    forced TB grain keeps the swapped scene.

    Strided forwards resolve for all three ops (the backward scenes are
    dilated, not reference fallbacks).  A forced policy on an op that
    genuinely cannot dispatch to Pallas (dgrad when padding exceeds the
    dilated filter extent minus one; dgrad *and* wgrad of a scene with
    explicit ``apad``) raises ``ValueError`` naming that op instead of
    silently returning a reference plan under a forced tag.
    """
    op = ConvOp(op)
    tag = policy_tag(policy)
    with default_tracer().span("repro.plan.make_plan") as sp:
        if sp:
            sp.set(op=op.value, policy=tag, scene=scene.describe())
        return _make_plan_inner(scene, op, policy, tag)


def _make_plan_inner(scene: ConvScene, op: ConvOp, policy: PolicySpec,
                     tag: str) -> ConvPlan:
    t_build = time.perf_counter()
    notes = []
    out_hw = None
    spec = None
    why = None                            # the recorded reference blocker
    choice = _batch_lanes_route(scene, op, policy, tag)
    # FPROP and the WBL route execute over the forward scene itself
    exec_scene: Optional[ConvScene] = (
        scene if op is ConvOp.FPROP or choice is not None else None)
    if op is ConvOp.DGRAD:
        why = _dgrad_blocker(scene)
        if why is None:
            exec_scene = grad_input_scene(scene)
    elif op is ConvOp.WGRAD and choice is None:
        why = _wgrad_blocker(scene)
        if why is None:
            exec_scene = grad_filter_scene(scene)
            out_hw = (scene.fltH, scene.fltW)   # trim stride-remainder rows
    uses_reference = why is not None
    if uses_reference:
        if tag.startswith("forced:"):
            raise ValueError(
                f"{op.value} of {scene.describe()} requires a reference "
                f"fallback ({why}); the forced policy {tag!r} cannot "
                f"be honored for this op")
        notes.append(f"{op.value}: {why}; exact jnp adjoint instead of Pallas")
        choice = None
    else:
        choice = choice or resolve_policy(exec_scene, policy)
        spec = derive_exec_spec(exec_scene, choice, out_hw)
    m = default_metrics()
    m.counter("repro.plan.builds").inc()
    if choice is not None and choice.schedule == BATCH_LANES:
        m.counter("repro.plan.wgrad_batch_lanes").inc()
    if uses_reference:
        m.counter("repro.plan.reference_fallbacks").inc()
    m.histogram("repro.plan.build_s").observe(time.perf_counter() - t_build)
    return ConvPlan(scene=scene, op=op, policy=tag,
                    uses_reference=uses_reference, notes=tuple(notes),
                    exec_scene=None if uses_reference else exec_scene,
                    choice=choice, spec=spec)


def _batch_lanes_route(scene: ConvScene, op: ConvOp, policy: PolicySpec,
                       tag: str) -> Optional[ScheduleChoice]:
    """The WBL choice when this plan takes that route: a wgrad under the
    analytic and tuned policies whenever the forward scene qualifies
    (``mapping.batch_lanes_choice``), and a pinned WBL choice (the
    registry's reload), which raises where the route does not serve.
    None otherwise: every forced grain keeps the swapped scene."""
    pinned = (isinstance(policy, ScheduleChoice)
              and policy.schedule == BATCH_LANES)
    if not pinned and (op is not ConvOp.WGRAD
                       or tag not in ("analytic", "tuned")):
        return None
    choice = None
    if op is ConvOp.WGRAD and _wgrad_blocker(scene) is None:
        choice = batch_lanes_choice(scene, model=_active_cost_model())
    if pinned and choice is None:
        raise ValueError(f"{BATCH_LANES} does not serve the {op.value} of "
                         f"{scene.describe()}: it computes the wgrad of "
                         f"IC under a sublane tile with dense taps")
    return policy if pinned else choice


def assemble_plan(scene: ConvScene, op: Union[ConvOp, str], policy: str,
                  choice: Optional[ScheduleChoice]) -> ConvPlan:
    """Rebuild a plan from a stored (scene, op, policy-tag, choice) without
    re-running resolution — the registry's deserialization path.  A stored
    choice is pinned exactly; a stored reference plan stays a reference
    plan.  Raises ``ValueError`` when the stored choice no longer matches
    what the op can execute (e.g. a Pallas choice for a strided dgrad)."""
    op = ConvOp(op)
    if choice is None:
        plan = make_plan(scene, op, policy="analytic")
        if not plan.uses_reference:
            raise ValueError(
                f"stored {op.value} plan for {scene.describe()} has no "
                f"schedule choice but the op does not require a reference "
                f"fallback")
        return dataclasses.replace(plan, policy=policy)
    plan = make_plan(scene, op, policy=choice)
    if plan.uses_reference:
        raise ValueError(
            f"stored {op.value} plan for {scene.describe()} pins "
            f"{choice.schedule} but the op requires a reference fallback")
    return dataclasses.replace(plan, policy=policy)
