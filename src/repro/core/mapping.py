"""Multi-grained mapping selector — the paper's core contribution, on TPU terms.

MG3MConv (paper §4.1.2) chooses a *thread-block granularity* per convolution
scene: TB(1,1) / TB(1,8) / TB(8,8).  On SW26010 those are zonings of the 8x8
CPE grid.  A TPU TensorCore has no CPE grid — the Pallas grid is a *sequential
pipeline* over one core — so the granularities translate to *grid schedules*
that trade VMEM residency (data reuse) against MXU tile utilization:

  TB11  whole-FLT VMEM residency, grid over spatial tasks only.
        = the paper's TB(1,1) small-scene mapping *and* its `outLen ->
        outH*outW` extreme filter reuse (Alg. 2): FLT is fetched from HBM
        exactly once.  Best when the MM_unit (OC, B, IC) is small.

  TB18  FLT is split along OC into slices that stay resident while the grid
        sweeps all spatial tasks; IN is refetched once per OC-slice pass.
        = TB(1,8): medium scenes where the full filter no longer fits VMEM.

  TB88  classic 2D-tiled GEMM per output pixel: grid blocks (bm, bn, bk) over
        (OC, B, IC*fltH*fltW) with a fp32 VMEM accumulator across reduction
        steps.  = TB(8,8): large scenes where one MM_unit alone can fill the
        machine.

The selector is an analytic roofline model (compute term vs HBM-traffic term,
with MXU tile-quantization waste) — the software analogue of paper Fig. 14.
The machine constants and per-scene-class correction factors live in a
``CostModel``: the default instance is the pure datasheet roofline, and
``repro.tune.calibrate`` fits corrected instances from measured tune records
so the same selector code can run either model.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.footprint import (LANE, SUBLANE, VMEM_BUDGET,
                                      batch_lanes_blocks, batch_lanes_strip,
                                      batch_lanes_vmem_bytes,
                                      vmem_bytes as _vmem_bytes)
from repro.core.scene import ConvScene, ceil_div, round_up

# TPU v5e per-chip peaks (Google Cloud documentation, "TPU v5e": 197
# TFLOP/s bf16, 16 GB HBM at 819 GB/s).  bf16 MXU rate; fp32 runs at half.
MXU_FLOPS_BF16 = 197e12
MXU_FLOPS_FP32 = MXU_FLOPS_BF16 / 2
HBM_BW = 819e9  # bytes/s
STEP_OVERHEAD_S = 150e-9 * 0.05  # amortized per-grid-step issue overhead
VMEM_BYTES = 16 * 2 ** 20  # VMEM_BUDGET (footprint) leaves headroom
MXU_DIM = 128

# Interconnect constants for mesh-sharded execution (repro.shard).  The
# joint grain x partition selector charges every inter-chip byte against
# ICI_BW and every collective round against ICI_LATENCY_S, plus a fixed
# per-dispatch shard_map launch cost — so a partition whose collective
# term erases its per-shard compute win loses to shards=1 by construction.
ICI_BW = 180e9                   # bytes/s per chip, one ring direction (v5e)
ICI_LATENCY_S = 1e-6             # per collective round (ppermute/psum hop)
SHARD_LAUNCH_OVERHEAD_S = 5e-6   # per sharded dispatch (shard_map glue)

SCHEDULES = ("TB11", "TB18", "TB88")
# The batch-on-lanes weight gradient: a route of WGRAD plans over the
# forward scene, not a grain the selector weighs (``batch_lanes_choice``).
BATCH_LANES = "WBL"

# Arithmetic-intensity band edges (FLOPs/byte) for cost-model scene classes.
AI_BAND_EDGES = (8.0, 64.0, 512.0)


def ai_band(ai: float) -> str:
    """Arithmetic-intensity band label used in cost-model class keys."""
    for i, edge in enumerate(AI_BAND_EDGES):
        if ai < edge:
            return f"ai{i}"
    return f"ai{len(AI_BAND_EDGES)}"


def class_key(schedule: str, bound: str, band: str) -> str:
    """Scene-class key: schedule x bound-type x arithmetic-intensity band."""
    return f"{schedule}|{bound}|{band}"


@dataclasses.dataclass(frozen=True)
class ClassCorrection:
    """Measured correction for one scene class (see ``tune/calibrate.py``).

    ``compute_scale``/``bw_scale`` multiply the datasheet rates into
    *effective* rates (<1 = slower than the roofline assumes);
    ``overhead_s`` replaces the per-grid-step overhead (None = keep the
    model's base overhead).
    """

    compute_scale: float = 1.0
    bw_scale: float = 1.0
    overhead_s: Optional[float] = None


_IDENTITY_CORRECTION = ClassCorrection()


@dataclasses.dataclass(frozen=True)
class CostModel:
    """Machine constants + per-class corrections behind the roofline model.

    The default instance is the uncalibrated v5e datasheet model.  Calibrated
    instances (``repro.tune.calibrate``) carry the same base constants plus
    ``corrections`` keyed by ``class_key(schedule, bound, ai_band)``; lookup
    falls back exact class -> "schedule|bound|*" -> "schedule|*|*" ->
    "*|*|*" -> identity.  The global tier matters: without it, a schedule
    with no measured records would be scored on raw datasheet rates and look
    arbitrarily faster than every calibrated (slowed-down) class.
    """

    mxu_flops_bf16: float = MXU_FLOPS_BF16
    mxu_flops_fp32: float = MXU_FLOPS_FP32
    hbm_bw: float = HBM_BW
    step_overhead_s: float = STEP_OVERHEAD_S
    corrections: Mapping[str, ClassCorrection] = dataclasses.field(
        default_factory=dict)
    source: str = "analytic"   # provenance: "analytic" or the artifact path

    def mxu_rate(self, dtype: str) -> float:
        return (self.mxu_flops_bf16 if jnp.dtype(dtype).itemsize <= 2
                else self.mxu_flops_fp32)

    def correction_for(self, schedule: str, bound: str, band: str
                       ) -> ClassCorrection:
        for key in (class_key(schedule, bound, band),
                    class_key(schedule, bound, "*"),
                    class_key(schedule, "*", "*"),
                    class_key("*", "*", "*")):
            corr = self.corrections.get(key)
            if corr is not None:
                return corr
        return _IDENTITY_CORRECTION

    @property
    def is_calibrated(self) -> bool:
        return bool(self.corrections)


# The v5e, the stated target: priced wherever no TPU is attached (CPU
# tests, compiles for a described chip).
DEFAULT_COST_MODEL = CostModel()
# Uncalibrated models keyed by ``jax.Device.device_kind``.  A TPU whose kind
# is missing here is an error (``device_cost_model``), not a default.
DEVICE_COST_MODELS: Dict[str, CostModel] = {"TPU v5 lite": DEFAULT_COST_MODEL}


def device_cost_model() -> CostModel:
    """The uncalibrated model of the chip this process runs on: on a TPU,
    the one of its ``device_kind`` (``ValueError`` for a kind with no
    entry); elsewhere ``DEFAULT_COST_MODEL``."""
    if jax.default_backend() != "tpu":
        return DEFAULT_COST_MODEL
    kind = jax.devices()[0].device_kind
    if kind not in DEVICE_COST_MODELS:
        raise ValueError(
            f"no published peaks for TPU device kind {kind!r} (known: "
            f"{sorted(DEVICE_COST_MODELS)}); add its entry to "
            f"repro.core.mapping.DEVICE_COST_MODELS")
    return DEVICE_COST_MODELS[kind]


@dataclasses.dataclass(frozen=True)
class ScheduleChoice:
    """A concrete grid schedule for one scene."""

    schedule: str          # TB11 | TB18 | TB88
    bm: int                # OC block
    bn: int                # B block
    bk: int                # IC block (reduction); TB11/TB18 use full IC
    predicted_s: float     # modeled runtime (seconds) on one v5e core
    compute_s: float
    hbm_s: float
    vmem_bytes: int
    notes: str = ""

    @property
    def bound(self) -> str:
        return "compute" if self.compute_s >= self.hbm_s else "memory"


def _dtype_bytes(dtype: str) -> int:
    return jnp.dtype(dtype).itemsize


def grid_steps(scene: ConvScene, bm: int, bn: int, bk: int) -> int:
    """Total pixel-steps of a blocked schedule over one scene: Pallas grid
    steps at one output pixel a step.  TB11/TB18 launch a strip of ``bw``
    pixels a step (``footprint.strip_width``), so their grids are ``bw``
    times shorter; the model still prices pixel-steps.

    Deliberately counts *all* ``fltH x fltW`` taps, not the dilation-reduced
    useful taps (``scene.taps_h/taps_w``): the kernels iterate every tap and
    burn a full MXU pass on the sentinel zeros of an lhs-dilated scene, so
    the compute/overhead terms must too.  Only ``scene.flops`` (useful work,
    the efficiency numerator) and the AI band shrink under dilation — which
    is exactly how ``select_schedule`` ranks dilated scenes honestly."""
    return (scene.num_spatial_tasks
            * ceil_div(scene.M, bm) * ceil_div(scene.N, bn)
            * scene.fltH * scene.fltW * ceil_div(scene.K, bk))


def _quantized_macs(scene: ConvScene, bm: int, bn: int, bk: int) -> float:
    """MACs the MXU actually burns, counting tile-quantization waste.

    Every dot issued by a grid step is (bm x bk) @ (bk x bn); the MXU executes
    it in ceil-divided 128x128x128 passes, so small blocks waste rows/cols —
    the TPU analogue of the paper's K%4 / N%16 padding waste (§4.4.2).
    """
    eff_m = round_up(min(bm, scene.M), MXU_DIM)
    eff_n = round_up(min(bn, scene.N), LANE)
    # The systolic array streams the contraction dim; quantization there is
    # only to the sublane tile.
    eff_k = round_up(min(bk, scene.K), SUBLANE)
    per_step = eff_m * eff_n * eff_k
    return per_step * grid_steps(scene, bm, bn, bk)


def _traffic_bytes(scene: ConvScene, schedule: str, bm: int, bn: int, bk: int) -> int:
    """HBM bytes moved under each schedule's residency pattern.

    The per-task input window counts all ``fltH x fltW`` tap fetches — on
    lhs-dilated scenes the hole taps still DMA the (zero) sentinel block,
    so dilation does not shrink the streamed traffic, only the useful
    FLOPs.  ``bytes_out`` already reflects the dilation-grown output."""
    it = _dtype_bytes(scene.dtype)
    flt = scene.fltH * scene.fltW * scene.K * scene.M * it
    in_win = scene.fltH * scene.fltW * scene.K * scene.N * it  # window per task
    tasks = scene.num_spatial_tasks
    out = scene.bytes_out()
    n_m = ceil_div(scene.M, bm)
    n_n = ceil_div(scene.N, bn)
    if schedule == "TB11":
        # FLT resident once; IN window streamed per task; OUT written once.
        return flt + tasks * in_win + out
    if schedule == "TB18":
        # one pass over all tasks per OC slice: IN re-streamed n_m times.
        return flt + n_m * tasks * in_win + out
    # TB88: per task, classic tile traffic: FLT slice per (m, n) pass.
    flt_per_task = flt  # each task needs the whole filter once per n-pass
    return tasks * (n_n * flt_per_task + n_m * in_win) + out


# The VMEM working-set arithmetic lives in repro.analysis.footprint (one
# formula shared with the tuner's space filter, the kernels' feasibility
# check, and the static verifier); _vmem_bytes above is that function.


def _score(scene: ConvScene, schedule: str, bm: int, bn: int, bk: int,
           model: Optional[CostModel] = None) -> Optional[ScheduleChoice]:
    model = model if model is not None else device_cost_model()
    vmem = _vmem_bytes(scene, schedule, bm, bn, bk)
    if vmem > VMEM_BUDGET:
        return None
    macs = _quantized_macs(scene, bm, bn, bk)
    raw_compute_s = 2 * macs / model.mxu_rate(scene.dtype)
    raw_hbm_s = _traffic_bytes(scene, schedule, bm, bn, bk) / model.hbm_bw
    # Scene class for correction lookup is decided on the *raw* roofline
    # terms — calibration buckets were built the same way, and deciding it
    # on corrected terms would make the class depend on its own correction.
    bound = "compute" if raw_compute_s >= raw_hbm_s else "memory"
    corr = model.correction_for(schedule, bound,
                                ai_band(scene.arithmetic_intensity))
    compute_s = raw_compute_s / max(corr.compute_scale, 1e-30)
    hbm_s = raw_hbm_s / max(corr.bw_scale, 1e-30)
    # Pallas fixed per-grid-step overhead (pipeline bubbles on tiny steps).
    per_step = (corr.overhead_s if corr.overhead_s is not None
                else model.step_overhead_s)
    overhead_s = grid_steps(scene, bm, bn, bk) * per_step
    total = max(compute_s, hbm_s) + overhead_s
    return ScheduleChoice(schedule, bm, bn, bk, total, compute_s, hbm_s, vmem)


def batch_lanes_choice(scene: ConvScene,
                       model: Optional[CostModel] = None
                       ) -> Optional[ScheduleChoice]:
    """The WBL route for the weight gradient of the *forward* ``scene``,
    or None when the scene does not take it.

    The swapped wgrad scene of an image-input stem puts IC (3) on the
    lanes, one output pixel a grid step.  WBL keeps the forward layout and
    contracts the batch on the lanes instead, so it serves scenes whose IC
    is under one sublane tile, with dense taps (no dilation), when the
    resident dFLT and a strip of dOUT columns fit ``VMEM_BUDGET``.  The
    choice carries bm=OC, bn=the batch lanes of a step (one lane tile),
    bk=IC padded to a sublane tile; its terms price the grid the kernel
    launches."""
    if (scene.IC >= SUBLANE or scene.dilH > 1 or scene.dilW > 1
            or scene.fdilH > 1 or scene.fdilW > 1
            or scene.apadH or scene.apadW):
        return None
    # Whole lane tiles: Mosaic cannot address the strip's window by
    # element offset in an array whose minor dim is under 128, so a
    # smaller batch is zero-padded to one lane tile.
    bn = LANE
    bw = batch_lanes_strip(scene, bn)
    if not bw:
        return None
    model = model if model is not None else device_cost_model()
    it = _dtype_bytes(scene.dtype)
    (in_lead, kp, _), _, (_, rows, _) = batch_lanes_blocks(scene, bn, bw)
    nb = ceil_div(scene.B, bn)
    steps = nb * scene.outH * (scene.outW // bw)
    pixels = nb * scene.outH * scene.outW
    macs = pixels * rows * bn * round_up(scene.OC, MXU_DIM)
    compute_s = 2 * macs / model.mxu_rate(scene.dtype)
    hbm_s = (steps * in_lead * kp * bn * it + scene.bytes_out()
             + rows * scene.OC * it) / model.hbm_bw
    return ScheduleChoice(
        BATCH_LANES, scene.OC, bn, kp,
        max(compute_s, hbm_s) + steps * model.step_overhead_s,
        compute_s, hbm_s, batch_lanes_vmem_bytes(scene, bn, bw))


def candidate_blocks(scene: ConvScene, schedule: str) -> Tuple[Tuple[int, int, int], ...]:
    """Hardware-aligned (bm, bn, bk) candidates per schedule.

    The enumeration lives in ``repro.tune.space`` (the autotuner's search
    space); the analytic selector prunes the same space, so a tuned cache
    entry is always a point the analytic path could also have chosen.
    """
    from repro.tune.space import block_candidates  # local: avoids import cycle
    return block_candidates(scene, schedule)


def select_schedule(scene: ConvScene,
                    allowed: Tuple[str, ...] = SCHEDULES,
                    model: Optional[CostModel] = None) -> ScheduleChoice:
    """Pick the best (schedule, blocks) for a scene — paper Fig. 14 in code.

    ``allowed`` restricts the grains considered (a forced schedule passes a
    1-tuple); when none of them fits VMEM at any candidate blocking, raises
    ``ValueError`` — a forced grain must never silently become another one.
    ``model`` swaps the cost model (default: uncalibrated roofline).
    """
    best: Optional[ScheduleChoice] = None
    for schedule in allowed:
        for bm, bn, bk in candidate_blocks(scene, schedule):
            choice = _score(scene, schedule, bm, bn, bk, model)
            if choice is not None and (best is None
                                       or choice.predicted_s < best.predicted_s):
                best = choice
    if best is None:
        # Nothing in `allowed` fits VMEM even fully blocked (huge IC*B).
        # TB88 can always shrink to minimal aligned tiles, so when it is
        # allowed, use that escape hatch; otherwise the requested grain is
        # genuinely infeasible and silently substituting a different kernel
        # would invalidate any forced-schedule comparison — raise instead.
        if "TB88" not in allowed:
            raise ValueError(
                f"forced schedule(s) {allowed} do not fit the VMEM budget "
                f"({VMEM_BUDGET} B) at any candidate blocking for "
                f"{scene.describe()}; allow TB88 (or use schedule=None) "
                f"for a tiled fallback")
        bm, bn, bk = (min(128, round_up(scene.M, SUBLANE)),
                      min(128, round_up(scene.N, LANE)),
                      min(128, round_up(scene.K, SUBLANE)))
        choice = _score(scene, "TB88", bm, bn, bk, model)
        if choice is None:
            raise ValueError(f"no feasible schedule for {scene.describe()}")
        best = choice
    return best


def granularity_map(b_values, c_values, dtype: str = "float32",
                    spatial: int = 14, flt: int = 3,
                    model: Optional[CostModel] = None
                    ) -> Dict[Tuple[int, int, int], str]:
    """Reproduce paper Fig. 14: best grain per (B, IC, OC) grid."""
    out = {}
    for b in b_values:
        for ic in c_values:
            for oc in c_values:
                scene = ConvScene(B=b, IC=ic, OC=oc, inH=spatial, inW=spatial,
                                  fltH=flt, fltW=flt, padH=flt // 2,
                                  padW=flt // 2, dtype=dtype)
                out[(b, ic, oc)] = select_schedule(scene, model=model).schedule
    return out


def predicted_efficiency(scene: ConvScene, choice: ScheduleChoice,
                         model: Optional[CostModel] = None) -> float:
    """Useful FLOPs / (peak FLOPs x modeled time) — the paper's
    'hardware efficiency' metric under the analytic model."""
    model = model if model is not None else device_cost_model()
    ideal_s = scene.flops / model.mxu_rate(scene.dtype)
    return min(1.0, ideal_s / max(choice.predicted_s, 1e-30))
