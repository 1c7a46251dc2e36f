"""Calibrate the roofline cost model from measured tune records.

Closes the measurement-to-model loop the autotuner left open (ROADMAP:
"Selector training data from tune artifacts"): every cache entry written by
``tune/autotune.py`` pairs an analytic prediction with a measured µs, and
this module fits per-scene-class correction factors over those pairs —

  effective compute rate   (the MXU never hits the datasheet number),
  effective HBM bandwidth  (neither does DMA),
  per-grid-step overhead   (pipeline bubbles dominate tiny-step schedules),

bucketed by scene class ``schedule x bound-type x arithmetic-intensity band``
(``mapping.class_key``).  Within a bucket the dominant roofline term is known,
so ``measured ≈ g*dominant + o*n_steps`` is an ordinary least-squares problem
in two features; thin buckets fall back to a median-ratio fit.  The result is
a ``mapping.CostModel`` whose corrected predictions the selector
(``select_schedule``) consumes unchanged — calibration swaps the constants,
not the selection code.

The fit persists as a versioned JSON artifact (same conventions as
``tune/cache.py``: schema + version fields, atomic tmp+rename write, env-var
path override).  ``active_cost_model()`` is the hot-path hook: it returns the
explicitly-installed model, else auto-loads the artifact (mtime-cached), else
the uncalibrated default — ``plan/build.resolve_policy`` and
``autotune.resolve_schedule`` route ``schedule=None`` / ``schedule="auto"``
cache misses through it.

Honesty caveats, recorded rather than hidden: proxy-capped measurements
calibrate the model *at the measured proxy geometry* (class bands are
computed on the measurement scene), and CPU-interpret µs calibrate a model of
the interpreter, not of a TPU — fit per backend (``backend=`` filter) and
re-fit after tuning on real hardware.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import sys
import tempfile
from typing import Dict, List, Optional, Tuple, Union

import numpy as np

from repro.core import mapping
from repro.core.mapping import ClassCorrection, CostModel, ai_band, class_key
from repro.core.scene import ConvScene
from repro.tune import cache as cache_mod

# Bump when the fit procedure or artifact layout changes meaning.
CALIB_VERSION = "mg3m-calib-v1"
ENV_VAR = "REPRO_CALIBRATION"
DEFAULT_PATH = os.path.join("~", ".cache", "repro", "calibration.json")
_SCHEMA = 1
# Below this many samples a bucket gets a median-ratio fit, not least squares
# (2 free parameters need >2 points to mean anything).
MIN_LSTSQ_SAMPLES = 3


def resolve_calibration_path(path: Optional[str] = None) -> str:
    """Explicit path > $REPRO_CALIBRATION > ~/.cache default."""
    p = path or os.environ.get(ENV_VAR) or DEFAULT_PATH
    return os.path.abspath(os.path.expanduser(p))


@dataclasses.dataclass(frozen=True)
class CalibSample:
    """One (prediction-terms, measured) training pair from the tune cache."""

    key: str               # cache signature the record came from
    cls: str               # scene-class key (on the measurement scene)
    schedule: str
    compute_s: float       # raw roofline compute term, measurement scene
    hbm_s: float           # raw roofline HBM term, measurement scene
    n_steps: int           # grid steps of the clipped blocking
    predicted_s: float     # uncalibrated total prediction
    measured_s: float      # wall-clocked truth from the tuned record
    scene: ConvScene       # measurement scene (proxy caps applied)
    bm: int
    bn: int
    bk: int


@dataclasses.dataclass(frozen=True)
class ClassFit:
    """Fitted correction + fit quality for one scene class."""

    cls: str
    n_samples: int
    compute_scale: float
    bw_scale: float
    overhead_s: float
    method: str            # "lstsq" | "ratio"
    median_err_before: float
    median_err_after: float


@dataclasses.dataclass
class CalibrationReport:
    """Everything a fit produced: the model plus its per-class audit."""

    classes: List[ClassFit]
    n_records: int
    n_skipped: int
    median_err_before: float
    median_err_after: float
    backend: Optional[str]
    source: str = "fit"

    def cost_model(self) -> CostModel:
        corrections = {
            f.cls: ClassCorrection(compute_scale=f.compute_scale,
                                   bw_scale=f.bw_scale,
                                   overhead_s=f.overhead_s)
            for f in self.classes}
        return CostModel(corrections=corrections, source=self.source)


def _make_sample(key: str, msc: ConvScene, schedule: str,
                 bm: int, bn: int, bk: int,
                 measured_us: float) -> Optional[CalibSample]:
    """Build one training pair for a clipped execution on the measurement
    scene, re-deriving the raw roofline terms it was predicted with."""
    bm, bn, bk = min(bm, msc.M), min(bn, msc.N), min(bk, msc.K)
    scored = mapping._score(msc, schedule, bm, bn, bk)
    if scored is None:
        return None
    cls = class_key(schedule, scored.bound, ai_band(msc.arithmetic_intensity))
    return CalibSample(
        key=key, cls=cls, schedule=schedule,
        compute_s=scored.compute_s, hbm_s=scored.hbm_s,
        n_steps=mapping.grid_steps(msc, bm, bn, bk),
        predicted_s=scored.predicted_s, measured_s=measured_us * 1e-6,
        scene=msc, bm=bm, bn=bn, bk=bk)


def samples_from_cache(cache: cache_mod.ScheduleCache, *,
                       backend: Optional[str] = None
                       ) -> Tuple[List[CalibSample], int]:
    """Extract training pairs from tuned records; returns (samples, skipped).

    Each record yields the measured *winner* pair and, when its execution
    differs from the winner's, the measured *analytic favorite* pair too
    (``analytic_measured_us`` is wall-clocked by the tuner and the favorite's
    blocks are deterministically reconstructable) — losing candidates are
    exactly the data that teaches the model why they lost.

    Skips records from other code versions / backends, non-finite or
    non-positive timings, and anything the schema validator rejects — a
    calibration must never crash on (or silently learn from) junk.
    """
    samples, skipped = [], 0
    for key, rec in cache.records().items():
        parts = cache_mod.parse_signature(key)
        if parts.get("v") != cache_mod.CODE_VERSION:
            skipped += 1
            continue
        if backend is not None and parts.get("be") != backend:
            skipped += 1
            continue
        if not cache_mod.valid_record(rec):
            skipped += 1
            continue
        measured_us = rec.get("measured_us")
        if not isinstance(measured_us, (int, float)) or \
                not math.isfinite(measured_us) or measured_us <= 0:
            skipped += 1
            continue
        try:
            scene = cache_mod.scene_from_signature(key)
            proxy = rec.get("proxy")
            msc = ConvScene(**{**scene.__dict__, **proxy}) if proxy else scene
            choice = cache_mod.choice_from_dict(rec["choice"])
        except (KeyError, TypeError, ValueError):
            skipped += 1
            continue
        # Measurement ran the wrapper-clipped blocking on the (possibly
        # proxy-capped) scene: re-derive the cost terms for exactly that.
        winner = _make_sample(key, msc, choice.schedule,
                              choice.bm, choice.bn, choice.bk, measured_us)
        if winner is None:
            skipped += 1
            continue
        samples.append(winner)

        # The analytic favorite's measured time, when it ran a different
        # kernel than the winner (equal clipped blocks = same measurement).
        a_us = rec.get("analytic_measured_us")
        a_sched = rec.get("analytic_schedule")
        if (isinstance(a_us, (int, float)) and math.isfinite(a_us)
                and a_us > 0 and a_sched in mapping.SCHEDULES):
            try:
                analytic = mapping.select_schedule(scene)
            except ValueError:
                analytic = None
            if analytic is not None and analytic.schedule == a_sched:
                fav = _make_sample(key, msc, analytic.schedule,
                                   analytic.bm, analytic.bn, analytic.bk,
                                   a_us)
                if fav is not None and (fav.schedule, fav.bm, fav.bn,
                                        fav.bk) != (winner.schedule,
                                                    winner.bm, winner.bn,
                                                    winner.bk):
                    samples.append(fav)
    return samples, skipped


def _ratio_fit(samples: List[CalibSample],
               base_overhead: float) -> Tuple[float, float, float, str]:
    """Median measured/predicted ratio applied to every term — exact when the
    real machine is a uniformly-scaled roofline, robust always."""
    r = _median([s.measured_s / max(s.predicted_s, 1e-30) for s in samples])
    if not math.isfinite(r) or r <= 0:
        return 1.0, 1.0, base_overhead, "ratio"
    return 1.0 / r, 1.0 / r, base_overhead * r, "ratio"


def _fit_bucket(cls: str, samples: List[CalibSample],
                base_overhead: float) -> Tuple[float, float, float, str]:
    """Fit (compute_scale, bw_scale, overhead_s) for one scene class.

    The class encodes the bound type, so the dominant roofline term is the
    same for every sample: solve ``measured ≈ g*dominant + o*n_steps`` by
    least squares, then invert ``g`` into an effective-rate scale.  Degenerate
    fits (negative rate, too few points) fall back to the ratio fit.
    """
    if len(samples) < MIN_LSTSQ_SAMPLES:
        return _ratio_fit(samples, base_overhead)
    bound = cls.split("|")[1]
    dom = np.array([s.compute_s if bound == "compute" else s.hbm_s
                    for s in samples])
    n = np.array([float(s.n_steps) for s in samples])
    y = np.array([s.measured_s for s in samples])
    X = np.stack([dom, n], axis=1)
    (g, o), *_ = np.linalg.lstsq(X, y, rcond=None)
    if o < 0:
        # Clamp the overhead at zero and refit the rate alone.
        o = 0.0
        denom = float(dom @ dom)
        g = float(dom @ y) / denom if denom > 0 else -1.0
    if not math.isfinite(g) or g <= 0:
        return _ratio_fit(samples, base_overhead)
    scale = 1.0 / float(g)
    return scale, scale, float(o), "lstsq"


def _rel_errors(samples: List[CalibSample],
                model: Optional[CostModel]) -> List[float]:
    errs = []
    for s in samples:
        scored = mapping._score(s.scene, s.schedule, s.bm, s.bn, s.bk, model)
        pred = scored.predicted_s if scored is not None else s.predicted_s
        errs.append(abs(pred - s.measured_s) / s.measured_s)
    return errs


def _median(xs: List[float]) -> float:
    if not xs:
        return float("nan")
    xs = sorted(xs)
    mid = len(xs) // 2
    return xs[mid] if len(xs) % 2 else (xs[mid - 1] + xs[mid]) / 2


def fit_calibration(cache: Union[cache_mod.ScheduleCache, List[CalibSample]],
                    *, backend: Optional[str] = None,
                    n_skipped: int = 0) -> CalibrationReport:
    """Fit per-class corrections over a tune cache (or pre-built samples)."""
    if isinstance(cache, cache_mod.ScheduleCache):
        samples, n_skipped = samples_from_cache(cache, backend=backend)
    else:
        samples = list(cache)
    buckets: Dict[str, List[CalibSample]] = {}
    for s in samples:
        buckets.setdefault(s.cls, []).append(s)
    # Aggregate tiers back unseen classes at selection time, one per level
    # of CostModel.correction_for's fallback chain: (schedule, bound) for
    # unseen AI bands, schedule for unseen bound types, global for
    # wholly-unmeasured schedules — without the global tier an unmeasured
    # schedule would be scored on raw datasheet rates and spuriously
    # dominate every calibrated (slowed-down) class.
    for s in samples:
        bound = s.cls.split("|")[1]
        buckets.setdefault(class_key(s.schedule, bound, "*"), []).append(s)
        buckets.setdefault(class_key(s.schedule, "*", "*"), []).append(s)
    if samples:
        buckets[class_key("*", "*", "*")] = list(samples)

    base_overhead = mapping.DEFAULT_COST_MODEL.step_overhead_s
    fits: Dict[str, Tuple[float, float, float, str]] = {}
    for cls, bucket in buckets.items():
        if "*" in cls:
            fits[cls] = _ratio_fit(bucket, base_overhead)
        else:
            fits[cls] = _fit_bucket(cls, bucket, base_overhead)

    model = CostModel(corrections={
        cls: ClassCorrection(compute_scale=cs, bw_scale=bs, overhead_s=ov)
        for cls, (cs, bs, ov, _) in fits.items()})

    classes = []
    for cls, bucket in sorted(buckets.items()):
        cs, bs, ov, method = fits[cls]
        # Audit each row against a model holding ONLY this class's
        # correction: under the full model, every sample's exact-class fit
        # would shadow the aggregate tiers and their error columns would
        # never exercise the correction the row reports.
        row_model = CostModel(corrections={
            cls: ClassCorrection(compute_scale=cs, bw_scale=bs,
                                 overhead_s=ov)})
        classes.append(ClassFit(
            cls=cls, n_samples=len(bucket), compute_scale=cs, bw_scale=bs,
            overhead_s=ov, method=method,
            median_err_before=_median(_rel_errors(bucket, None)),
            median_err_after=_median(_rel_errors(bucket, row_model))))
    return CalibrationReport(
        classes=classes, n_records=len(samples), n_skipped=n_skipped,
        median_err_before=_median(_rel_errors(samples, None)),
        median_err_after=_median(_rel_errors(samples, model)),
        backend=backend)


# -- artifact persistence (tune/cache.py conventions) ------------------------
def save_calibration(report: CalibrationReport,
                     path: Optional[str] = None) -> str:
    """Write the fit as a versioned JSON artifact (atomic tmp+rename)."""
    p = resolve_calibration_path(path)
    base = mapping.DEFAULT_COST_MODEL
    doc = {
        "schema": _SCHEMA,
        "version": CALIB_VERSION,
        "tune_version": cache_mod.CODE_VERSION,
        "backend": report.backend,
        "n_records": report.n_records,
        "n_skipped": report.n_skipped,
        "median_err_before": report.median_err_before,
        "median_err_after": report.median_err_after,
        "base": {"mxu_flops_bf16": base.mxu_flops_bf16,
                 "mxu_flops_fp32": base.mxu_flops_fp32,
                 "hbm_bw": base.hbm_bw,
                 "step_overhead_s": base.step_overhead_s},
        "corrections": {
            f.cls: {"compute_scale": f.compute_scale,
                    "bw_scale": f.bw_scale, "overhead_s": f.overhead_s,
                    "n_samples": f.n_samples, "method": f.method,
                    "median_err_before": f.median_err_before,
                    "median_err_after": f.median_err_after}
            for f in report.classes},
    }
    os.makedirs(os.path.dirname(p), exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(p), suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        os.replace(tmp, p)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return p


def load_calibration(path: Optional[str] = None) -> CostModel:
    """Load a calibration artifact into a usable ``CostModel`` (strict)."""
    p = resolve_calibration_path(path)
    with open(p) as f:
        doc = json.load(f)
    if doc.get("version") != CALIB_VERSION:
        raise ValueError(
            f"calibration artifact {p} has version "
            f"{doc.get('version')!r}, expected {CALIB_VERSION!r}; re-fit "
            f"with scripts/calibrate.py")
    base = doc.get("base", {})
    corrections = {}
    for cls, c in doc.get("corrections", {}).items():
        corrections[cls] = ClassCorrection(
            compute_scale=float(c["compute_scale"]),
            bw_scale=float(c["bw_scale"]),
            overhead_s=(None if c.get("overhead_s") is None
                        else float(c["overhead_s"])))
    dflt = mapping.DEFAULT_COST_MODEL
    return CostModel(
        mxu_flops_bf16=float(base.get("mxu_flops_bf16", dflt.mxu_flops_bf16)),
        mxu_flops_fp32=float(base.get("mxu_flops_fp32", dflt.mxu_flops_fp32)),
        hbm_bw=float(base.get("hbm_bw", dflt.hbm_bw)),
        step_overhead_s=float(base.get("step_overhead_s",
                                       dflt.step_overhead_s)),
        corrections=corrections, source=p)


# -- process-wide active model (consulted on schedule=None/"auto" misses) ----
_active: Optional[CostModel] = None
# path -> (mtime, model-or-None); None caches a failed load until the file
# changes, so a corrupt artifact warns once instead of once per conv call.
_autoload: Dict[str, Tuple[float, Optional[CostModel]]] = {}


def set_active_cost_model(model: Optional[CostModel]) -> None:
    """Install (or with None, reset to artifact auto-loading) the cost model
    used by schedule resolution — used by the CLI and tests."""
    global _active
    _active = model


def active_cost_model() -> CostModel:
    """Cost model for selection right now: explicitly-installed model, else
    the calibration artifact at the resolved path (auto-reloaded when its
    mtime changes), else the uncalibrated roofline of this chip
    (``mapping.device_cost_model``)."""
    if _active is not None:
        return _active
    p = resolve_calibration_path()
    try:
        mtime = os.path.getmtime(p)
    except OSError:
        return mapping.device_cost_model()
    cached = _autoload.get(p)
    if cached is None or cached[0] != mtime:
        model: Optional[CostModel] = None
        try:
            model = load_calibration(p)
        except Exception as e:  # noqa: BLE001 — any malformed artifact must
            # fall back to the analytic model, never crash schedule
            # resolution (the tune-cache equivalent is valid_record()).
            print(f"repro.tune: ignoring unusable calibration {p}: {e}",
                  file=sys.stderr)
        _autoload[p] = (mtime, model)
        cached = _autoload[p]
    return (cached[1] if cached[1] is not None
            else mapping.device_cost_model())
