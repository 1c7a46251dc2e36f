"""Empirical Table 2: analytic-only vs measured-tuned schedule selection.

For each scene the autotuner reports the measured µs of both the analytic
roofline favorite and the empirically-picked winner (cache-hitting if
``scripts/tune.py`` already tuned the scene into the default cache, so this
table is cheap to re-emit after a batch tune).  Wall times follow the
``benchmarks/common.py`` honesty conventions: proxy-capped, CPU-interpret,
relative-ordering numbers — not TPU truth.
"""
from repro.core.mapping import select_schedule
from repro.models.cnn import cnn_scenes
from repro.tune import autotune_scene
from benchmarks.common import emit


def rows(nets=("vgg",), batch=8, limit=2, top_k=3, iters=2):
    out = []
    all_scenes = cnn_scenes(batch)
    for net in nets:
        scenes = all_scenes[net][:limit] if limit else all_scenes[net]
        for i, sc in enumerate(scenes):
            t = autotune_scene(sc, top_k=top_k, iters=iters,
                               measure_batch=2, measure_max_ch=16,
                               measure_max_hw=8)
            a = select_schedule(sc)
            speedup = t.analytic_measured_us / max(t.measured_us, 1e-9)
            out.append((
                f"tuned_{net}_L{i}", t.measured_us,
                f"analytic={a.schedule}@{t.analytic_measured_us:.1f}us;"
                f"tuned={t.choice.schedule}"
                f"({t.choice.bm}/{t.choice.bn}/{t.choice.bk});"
                f"speedup={speedup:.2f}x;pred_err={t.prediction_error:.3f}"))
    return out


def main():
    emit(rows())


if __name__ == "__main__":
    main()
