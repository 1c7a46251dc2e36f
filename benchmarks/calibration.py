"""Calibration table: analytic vs calibrated vs tuned schedule agreement.

For each scene: tune it (cache-hitting if ``scripts/tune.py`` already ran),
fit a calibration over everything the cache now holds, and compare three
selectors against the measured winner — the uncalibrated roofline, the
calibrated cost model, and the tuned cache itself (trivially in agreement,
shown as the reference).  The error columns are the per-scene
|predicted-measured|/measured of the winner's time under each model.

Wall times follow the ``benchmarks/common.py`` honesty conventions:
proxy-capped, CPU-interpret, relative-ordering numbers — not TPU truth.
"""
from repro.core.mapping import select_schedule
from repro.models.cnn import cnn_scenes
from repro.tune import autotune_scene, default_cache, fit_calibration
from benchmarks.common import emit


def rows(nets=("vgg",), batch=8, limit=2, top_k=3, iters=2):
    cache = default_cache()
    tuned = []
    all_scenes = cnn_scenes(batch)
    for net in nets:
        scenes = all_scenes[net][:limit] if limit else all_scenes[net]
        for i, sc in enumerate(scenes):
            t = autotune_scene(sc, cache=cache, top_k=top_k, iters=iters,
                               measure_batch=2,
                               measure_max_ch=16, measure_max_hw=8)
            tuned.append((f"{net}_L{i}", sc, t))

    report = fit_calibration(cache)
    model = report.cost_model()

    out = []
    agree_a = agree_c = 0
    for name, sc, t in tuned:
        analytic = select_schedule(sc)
        calibrated = select_schedule(sc, model=model)
        a_ok = analytic.schedule == t.choice.schedule
        c_ok = calibrated.schedule == t.choice.schedule
        agree_a += a_ok
        agree_c += c_ok
        out.append((
            f"calib_{name}", t.measured_us,
            f"tuned={t.choice.schedule};analytic={analytic.schedule}"
            f"(agree={int(a_ok)});calibrated={calibrated.schedule}"
            f"(agree={int(c_ok)});pred_err={t.prediction_error:.3f}"))
    out.append((
        "calib_summary", 0.0,
        f"scenes={len(tuned)};analytic_agree={agree_a}/{len(tuned)};"
        f"calibrated_agree={agree_c}/{len(tuned)};"
        f"median_err_roofline={report.median_err_before:.3f};"
        f"median_err_calibrated={report.median_err_after:.3f};"
        f"classes={len(report.classes)}"))
    return out


def main():
    emit(rows())


if __name__ == "__main__":
    main()
