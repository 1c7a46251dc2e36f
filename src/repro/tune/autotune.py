"""Autotune orchestration: analytic pruning -> measurement -> cached pick.

Pipeline per scene (cuDNN-style heuristic-seeded empirical search):

  1. ``space.ranked_space`` enumerates every feasible (schedule, bm, bn, bk)
     point and ranks it with the analytic roofline model (the pruner);
  2. the top-k survivors are wall-clocked through the real kernel dispatch
     (``measure.measure_choice``), optionally on a capped proxy scene;
  3. the measured winner is recorded as a ``TunedChoice`` — alongside the
     analytic model's own favorite and its prediction error, so every tuning
     run doubles as an audit of how wrong the static cost model is.

``resolve_schedule`` is the hot-path entry: cache hit -> cached choice,
miss -> analytic fallback.  It NEVER tunes implicitly — measurement only
happens through ``autotune_scene`` / ``scripts/tune.py``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List, Optional

from repro.core import mapping
from repro.core.mapping import ScheduleChoice, select_schedule
from repro.core.scene import ConvScene
from repro.obs import drift as drift_mod
from repro.obs.metrics import default_metrics
from repro.obs.trace import default_tracer
from repro.tune import cache as cache_mod
from repro.tune import measure as measure_mod
from repro.tune import space as space_mod

MeasureFn = Callable[[ConvScene, ScheduleChoice], float]


def error_summary(errors: List[float]) -> Dict[str, float]:
    """Aggregate prediction errors with non-finite rows excluded and counted.

    A ``prediction_error=inf`` row (an all-candidates-timed-out tune) would
    poison ``mean``/``max`` into ``inf`` — report it as a *count* instead,
    so the audit trail distinguishes "the model is 30% off" from "two scenes
    never produced a timing"."""
    finite = [e for e in errors if math.isfinite(e)]
    return {
        "n": len(errors),
        "n_finite": len(finite),
        "n_nonfinite": len(errors) - len(finite),
        "mean": sum(finite) / len(finite) if finite else float("nan"),
        "max": max(finite) if finite else float("nan"),
    }


@dataclasses.dataclass(frozen=True)
class TunedChoice:
    """Outcome of tuning one scene."""

    choice: ScheduleChoice         # measured winner (full-scene blocks)
    measured_us: float             # winner's median wall time
    analytic_schedule: str         # what the roofline model alone would pick
    analytic_predicted_us: float   # its predicted time (measurement scene)
    analytic_measured_us: float    # its measured time (measurement scene)
    prediction_error: float        # |measured - predicted| / measured, winner
    n_candidates: int              # how many points were wall-clocked
    backend: str                   # cache-key backend tag
    proxy: Optional[Dict] = None   # caps used for measurement, None = exact

    @property
    def agrees_with_analytic(self) -> bool:
        return self.choice.schedule == self.analytic_schedule

    def to_record(self) -> Dict:
        d = dataclasses.asdict(self)
        d["choice"] = cache_mod.choice_to_dict(self.choice)
        return d

    @classmethod
    def from_record(cls, rec: Dict) -> "TunedChoice":
        d = dict(rec)
        d["choice"] = cache_mod.choice_from_dict(rec["choice"])
        return cls(**d)


def _predicted_us(scene: ConvScene, choice: ScheduleChoice) -> float:
    """Analytic prediction for this point *on the measurement scene* (blocks
    clipped the same way the kernel wrapper clips them)."""
    scored = mapping._score(scene, choice.schedule,
                            min(choice.bm, scene.M), min(choice.bn, scene.N),
                            min(choice.bk, scene.K))
    return (scored.predicted_s if scored else choice.predicted_s) * 1e6


def autotune_scene(scene: ConvScene, *,
                   cache: Optional[cache_mod.ScheduleCache] = None,
                   top_k: int = 4, iters: int = 3, warmup: int = 1,
                   timeout_s: float = 120.0,
                   measure_batch: Optional[int] = None,
                   measure_max_ch: Optional[int] = None,
                   measure_max_hw: Optional[int] = None,
                   force: bool = False,
                   measure_fn: Optional[MeasureFn] = None) -> TunedChoice:
    """Tune one scene; consults/updates ``cache`` (default process cache).

    ``measure_fn`` overrides the wall-clock harness (tests inject synthetic
    timings); the default times the scene's fprop ``ConvPlan.execute``.
    """
    cache = cache if cache is not None else cache_mod.default_cache()
    backend = cache_mod.default_backend()
    if not force:
        rec = cache.get(scene, backend)
        if rec is not None:
            return TunedChoice.from_record(rec)

    candidates: List[ScheduleChoice] = space_mod.ranked_space(
        scene, top_k=max(top_k, 1))
    analytic = select_schedule(scene)

    msc = measure_mod.proxy_scene(scene, measure_batch=measure_batch,
                                  measure_max_ch=measure_max_ch,
                                  measure_max_hw=measure_max_hw)
    proxy = None
    if msc != scene:
        proxy = {"B": msc.B, "IC": msc.IC, "OC": msc.OC,
                 "inH": msc.inH, "inW": msc.inW}
    if measure_fn is None:
        measure_fn = lambda s, c: measure_mod.measure_choice(
            s, c, iters=iters, warmup=warmup,
            timeout_s=timeout_s)

    # The kernel wrapper clips blocks to the measurement scene's dims, so on
    # a small proxy several full-scene candidates can alias to the *same*
    # executed kernel; measuring aliases separately would just rank noise.
    # Keep the analytically-best representative of each distinct execution.
    clip = lambda c: (c.schedule, min(c.bm, msc.M), min(c.bn, msc.N),
                      min(c.bk, msc.K))
    distinct: Dict = {}
    for c in candidates:
        distinct.setdefault(clip(c), c)
    with default_tracer().span("repro.tune.scene", scene=scene.describe(),
                               backend=backend,
                               n_candidates=len(distinct)):
        timings = [(measure_fn(msc, c), c) for c in distinct.values()]
    best_us, best = min(timings, key=lambda t: t[0])
    default_metrics().counter("repro.tune.scenes_tuned").inc()
    if not math.isfinite(best_us):
        default_metrics().counter("repro.tune.tune_failures").inc()
        # Every candidate failed to produce a timing: fall back to the
        # analytic choice and do NOT cache — a poisoned entry would pin the
        # schedule="auto" path to a known-broken kernel.
        return TunedChoice(
            choice=analytic, measured_us=best_us,
            analytic_schedule=analytic.schedule,
            analytic_predicted_us=_predicted_us(msc, analytic),
            analytic_measured_us=best_us,
            prediction_error=float("inf"), n_candidates=len(timings),
            backend=backend, proxy=proxy)

    # The analytic favorite's measured time, for the tuned-vs-analytic table;
    # reuse the timing if its *clipped* execution was already wall-clocked —
    # comparing full-scene blocks here would re-measure a kernel that is
    # identical once the wrapper clips it to the measurement scene.
    analytic_us = next(
        (us for us, c in timings if clip(c) == clip(analytic)), None)
    if analytic_us is None:
        analytic_us = measure_fn(msc, analytic)

    predicted_us = _predicted_us(msc, best)
    err = abs(best_us - predicted_us) / best_us if best_us > 0 else float("inf")
    # Every tuning run doubles as a drift observation: the winner's
    # (predicted, measured) pair streams into the per-scene-class monitor
    # (non-finite pairs are dropped and counted there, never averaged).
    drift_mod.default_monitor().observe(
        drift_mod.scene_class(msc, best),
        predicted_us * 1e-6, best_us * 1e-6)
    tuned = TunedChoice(
        choice=best, measured_us=best_us,
        analytic_schedule=analytic.schedule,
        analytic_predicted_us=_predicted_us(msc, analytic),
        analytic_measured_us=analytic_us,
        prediction_error=err, n_candidates=len(timings),
        backend=backend, proxy=proxy)
    cache.put(scene, tuned.to_record(), backend)
    return tuned


def resolve_schedule(scene: ConvScene, *,
                     cache: Optional[cache_mod.ScheduleCache] = None
                     ) -> ScheduleChoice:
    """``schedule="auto"`` resolution: tuned cache first; on a miss, select
    under the active cost model (calibrated when an artifact exists — see
    ``tune/calibrate.py`` — else the analytic roofline).

    Never measures — the hot path must not block on a tuning run."""
    cache = cache if cache is not None else cache_mod.default_cache()
    choice = cache.get_choice(scene, cache_mod.default_backend())
    if choice is not None:
        return choice
    from repro.tune import calibrate as calibrate_mod  # local: import order
    return select_schedule(scene, model=calibrate_mod.active_cost_model())
