"""Static plan/schedule verifier: clean-tree sweeps, seeded-bug mutation
coverage (every bug class the verifier exists to catch, via
``dataclasses.replace`` on a good ``KernelGridSpec``), and the
single-source VMEM-footprint regression."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import repro.analysis.verify as verify_mod
import repro.kernels.mg3m_conv as mg
from repro.analysis import footprint
from repro.analysis.verify import (_spec_for, check_batch_lanes_spec,
                                   check_spec, sweep_scene, sweep_scenes,
                                   verify_plan, verify_point)
from repro.core import mapping
from repro.core.mapping import ScheduleChoice
from repro.core.scene import ConvScene
from repro.models.cnn import cnn_layer_scenes
from repro.plan import ConvOp, make_plan
from repro.plan.build import launched_shapes
from repro.tune import space as tune_space

DENSE = ConvScene(B=4, IC=8, OC=16, inH=8, inW=8, fltH=3, fltW=3,
                  padH=1, padW=1)
STRIDED = ConvScene(B=4, IC=8, OC=16, inH=10, inW=10, fltH=3, fltW=3,
                    padH=1, padW=1, stdH=2, stdW=2)
# the dgrad-shaped scene class: lhs-dilated + asymmetric pad -> sentinel route
DILATED = ConvScene(B=2, IC=8, OC=16, inH=5, inW=5, fltH=3, fltW=3,
                    padH=1, padW=1, dilH=2, dilW=2, apadH=1, apadW=1)


def _spec(scene, schedule="TB11", bm=0, bn=0, bk=0):
    choice = ScheduleChoice(schedule, bm or scene.M, bn or scene.N,
                            bk or scene.K, 0.0, 0.0, 0.0, 0)
    spec, bad = _spec_for(scene, choice)
    assert bad is None, bad
    return spec


def _codes(findings):
    return {f.code for f in findings}


# --------------------------------------------------------------------------
# clean tree: zero findings, no kernel execution
# --------------------------------------------------------------------------
@pytest.mark.parametrize("scene", [DENSE, STRIDED, DILATED],
                         ids=["dense", "strided", "dilated"])
@pytest.mark.parametrize("schedule", ["TB11", "TB18", "TB88"])
def test_verify_point_clean(scene, schedule):
    blocks = {} if schedule == "TB11" else dict(bm=8, bn=128, bk=8)
    assert verify_point(scene, schedule, **blocks) == []


@pytest.mark.parametrize("op", list(ConvOp))
def test_verify_plan_clean_all_ops(op):
    assert verify_plan(make_plan(STRIDED, op)) == []


# an image-input stem: its wgrad takes the WBL route (outW 8: two strips)
STEM = ConvScene(B=8, IC=3, OC=16, inH=16, inW=16, fltH=7, fltW=7,
                 padH=3, padW=3, stdH=2, stdW=2)


def _wbl_spec(scene=STEM, bw=4):
    plan = make_plan(scene, ConvOp.WGRAD)
    in_shape, dout_shape = launched_shapes(plan.exec_scene, plan.spec)
    return mg.batch_lanes_grid_spec(scene, in_shape=in_shape,
                                    dout_shape=dout_shape, bn=plan.spec.bn,
                                    bw=bw)


def test_verify_plan_clean_batch_lanes():
    plan = make_plan(STEM, ConvOp.WGRAD)
    assert plan.schedule == "WBL" and verify_plan(plan) == []
    for bw in (1, 2, 4, 8):
        assert check_batch_lanes_spec(_wbl_spec(bw=bw)) == []


def test_sweep_scene_covers_all_ops_and_points():
    findings, checked = sweep_scene(STRIDED)
    assert findings == []
    # at least one feasible point per op survives the VMEM filter
    assert checked >= 3


def test_sweep_paper_scenes_clean():
    scenes = cnn_layer_scenes(batch=1, max_hw=14, max_ch=32)
    findings, checked = sweep_scenes(scenes)
    assert findings == {}
    assert checked > 100


def test_reference_plan_has_nothing_to_verify():
    # over-padded 1x1 dgrad is blocked -> reference path: no Pallas geometry
    sc = ConvScene(B=1, IC=2, OC=2, inH=6, inW=6, fltH=1, fltW=1,
                   padH=1, padW=1)
    plan = make_plan(sc, ConvOp.DGRAD)
    assert plan.uses_reference and verify_plan(plan) == []


# --------------------------------------------------------------------------
# mutation coverage: each seeded bug class is flagged, actionably
# --------------------------------------------------------------------------
def test_mutation_shifted_output_tile():
    spec = _spec(DENSE, "TB18", bm=8)
    bad = dataclasses.replace(
        spec, out_index=lambda mm, oh, ow, i, j: (oh, ow, mm + 1, 0))
    codes = _codes(check_spec(bad))
    assert "out-coverage" in codes


def test_mutation_collapsed_output_tiles_overlap():
    spec = _spec(DENSE, "TB11")
    bad = dataclasses.replace(
        spec, out_index=lambda oh, ow, i, j: (0, ow, 0, 0))
    codes = _codes(check_spec(bad))
    assert "out-overlap" in codes


def _unscaled_strip_window(spec):
    """The strip's input window placed at column ``ow * stdW`` instead of
    ``ow * bw * stdW``: strip ``ow`` overlaps its neighbour's window."""
    sc = spec.scene
    return dataclasses.replace(spec, in_index=lambda oh, ow, i, j: (
        oh * sc.stdH + i * sc.fdilH, ow * sc.stdW + j * sc.fdilW, 0, 0))


def _overlapping_strip_store(spec):
    """The last strip stored over its neighbour's output columns."""
    last = spec.grid[1] - 1
    return dataclasses.replace(spec, out_index=lambda oh, ow, i, j: (
        oh, np.minimum(ow, last - 1), 0, 0))


@pytest.mark.parametrize("plant,code", [
    (_unscaled_strip_window, "index-map-mismatch"),
    (_overlapping_strip_store, "out-overlap"),
], ids=["input-window", "output-strip"])
def test_mutation_strip_overlaps_neighbour(plant, code):
    sc = ConvScene(B=4, IC=8, OC=16, inH=6, inW=16, fltH=3, fltW=3,
                   padH=1, padW=1, stdH=2, stdW=2)  # outW 8: two strips of 4
    spec = mg.kernel_grid_spec(sc, "TB11",
                               in_shape=(sc.inH + 2, sc.inW + 2, sc.K, sc.N),
                               flt_shape=sc.flt_shape(), bw=4)
    assert spec.grid[1] == 2
    assert check_spec(spec) == []
    assert code in _codes(check_spec(plant(spec)))


def _wbl_window_short(spec):
    h, w, k, n = spec.in_block
    return dataclasses.replace(spec, in_block=(h, w - 1, k, n))


def _wbl_window_shifted(spec):
    sc, bw, orig = spec.scene, spec.strip, spec.in_index
    return dataclasses.replace(spec, in_index=lambda nb, oh, st: (
        orig(nb, oh, st)[0], st * bw * sc.stdW + 1, 0, nb * spec.in_block[3]))


def _wbl_row_off_by_one(spec):
    orig = spec.in_index
    return dataclasses.replace(spec, in_index=lambda nb, oh, st: (
        orig(nb, oh, st)[0] + 1, *orig(nb, oh, st)[1:]))


@pytest.mark.parametrize("plant,codes", [
    (_wbl_window_short, {"window-bounds"}),
    (_wbl_window_shifted, {"index-map-mismatch"}),
    (_wbl_row_off_by_one, {"index-map-mismatch"}),
    (lambda s: dataclasses.replace(
        s, dout_index=lambda nb, oh, st: (oh, 0 * st, 0, nb)),
     {"out-coverage"}),
    (lambda s: dataclasses.replace(s, taps=(s.taps[0], s.taps[1] - 1)),
     {"dropped-tap"}),
    (lambda s: dataclasses.replace(
        s, out_index=lambda nb, oh, st: (0 * nb + oh % 2, 0)),
     {"reduction-dependence"}),
], ids=["window-one-short", "window-one-right", "row-one-down",
        "strip-revisited", "tap-dropped", "dflt-moves"])
def test_mutation_batch_lanes_geometry(plant, codes):
    spec = _wbl_spec()
    assert spec.grid[2] == 2
    assert codes <= _codes(check_batch_lanes_spec(plant(spec)))


def test_flagged_batch_lanes_window_really_diverges():
    # the window one column to the right computes a wrong dFLT
    import jax

    from repro.plan.build import _ref_wgrad

    sc, good = STEM, _wbl_spec()
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    real = jax.random.normal(k1, sc.in_shape(), jnp.float32)
    d_real = jax.random.normal(k2, sc.out_shape(), jnp.float32)
    kp, n = good.in_shape[2:]
    inp = jnp.pad(real, ((sc.padH,) * 2, (sc.padW,) * 2, (0, kp - sc.IC),
                         (0, n - sc.B)))
    d_out = jnp.pad(d_real, ((0, 0), (0, 0), (0, 0), (0, n - sc.B)))
    want = np.asarray(_ref_wgrad(real, d_real, sc))

    def launch(spec):
        out = mg._launch_batch_lanes(spec, inp, d_out, interpret=True)
        return np.asarray(out).reshape(sc.fltH, sc.fltW, kp,
                                       sc.OC)[:, :, :sc.IC]

    np.testing.assert_allclose(launch(good), want, rtol=1e-4, atol=1e-4)
    assert not np.allclose(launch(_wbl_window_shifted(good)), want,
                           rtol=1e-3, atol=1e-2)


def test_mutation_output_moves_with_reduction():
    spec = _spec(DENSE, "TB11")
    bad = dataclasses.replace(
        spec, out_index=lambda oh, ow, i, j: (oh, ow, i, 0))
    codes = _codes(check_spec(bad))
    assert "reduction-dependence" in codes


def test_mutation_dropped_filter_tap():
    spec = _spec(DENSE, "TB11")
    g = spec.grid
    bad = dataclasses.replace(spec, grid=(g[0], g[1], g[2] - 1, g[3]),
                              reduction_extents=(g[2] - 1, g[3]))
    codes = _codes(check_spec(bad))
    assert "dropped-tap" in codes
    assert "grid-steps-disagree" in codes


def test_mutation_sentinel_miss_reads_dilation_hole():
    spec = _spec(DILATED, "TB11")
    sc = DILATED

    def dense_style(oh, ow, i, j):  # pretends the input were pre-padded
        return (np.minimum(oh * sc.stdH + i, sc.inH),
                np.minimum(ow * sc.stdW + j, sc.inW), 0, 0)

    codes = _codes(check_spec(dataclasses.replace(spec,
                                                  in_index=dense_style)))
    assert "sentinel-miss" in codes


def test_mutation_live_taps_sent_to_sentinel():
    spec = _spec(DILATED, "TB11")
    bad = dataclasses.replace(
        spec,
        in_index=lambda oh, ow, i, j: (DILATED.inH, DILATED.inW, 0, 0))
    findings = check_spec(bad)
    assert "dropped-tap" in _codes(findings)
    # the message carries everything needed to reproduce: scene + schedule
    msg = next(f for f in findings if f.code == "dropped-tap").message
    assert "TB11" in msg and "scene(" in msg


def test_mutation_vmem_overshoot():
    spec = _spec(DENSE, "TB11")
    codes = _codes(check_spec(spec, vmem_budget=1024))
    assert "vmem-overshoot" in codes


def test_mutation_accumulator_demoted():
    spec = _spec(DENSE, "TB11")
    bad = dataclasses.replace(spec, acc_dtype=jnp.bfloat16)
    codes = _codes(check_spec(bad))
    assert "dtype-promotion" in codes


def test_mutation_input_block_out_of_bounds():
    spec = _spec(DENSE, "TB88", bm=8, bn=128, bk=8)
    orig = spec.in_index

    def shifted(*gc):
        ih, iw, kk, nn = orig(*gc)
        return ih, iw, kk + spec.grid[-1], nn  # one K-block past the end

    codes = _codes(check_spec(dataclasses.replace(spec, in_index=shifted)))
    assert "in-bounds" in codes


def test_findings_name_scene_and_schedule():
    spec = _spec(STRIDED, "TB18", bm=8)
    bad = dataclasses.replace(
        spec, out_index=lambda mm, oh, ow, i, j: (0, 0, 0, 0))
    findings = check_spec(bad)
    assert findings
    for f in findings:
        assert f.schedule == "TB18"
        assert f.scene == STRIDED.describe()
        assert f.message  # self-contained, non-empty


# --------------------------------------------------------------------------
# one footprint formula for the whole stack
# --------------------------------------------------------------------------
def test_single_footprint_source():
    # selection, tuning-space filter, kernel guard, verifier: same function
    # (the kernel guard and the verifier through the launch's count, which
    # adds the strip)
    assert mapping._vmem_bytes is footprint.vmem_bytes
    assert tune_space.vmem_bytes is footprint.vmem_bytes
    assert mg.launch_vmem_bytes is footprint.launch_vmem_bytes
    assert verify_mod.launch_vmem_bytes is footprint.launch_vmem_bytes


def test_footprint_pinned_bytes():
    # K=8, N=4, M=16, 3x3 filter, fp32: hand-computed working sets
    sc = ConvScene(B=4, IC=8, OC=16, inH=8, inW=8, fltH=3, fltW=3,
                   padH=1, padW=1)
    # TB11: 2*(4608 + 128 + 256) + 4*16*4
    assert footprint.vmem_bytes(sc, "TB11", 16, 4, 8) == 10240
    # TB18 bm=8: 2*(2304 + 128 + 128) + 4*8*4
    assert footprint.vmem_bytes(sc, "TB18", 8, 4, 8) == 5248
    # TB88 8/4/8: 2*(256 + 128 + 128) + 4*8*4
    assert footprint.vmem_bytes(sc, "TB88", 8, 4, 8) == 1152
    with pytest.raises(ValueError):
        footprint.vmem_bytes(sc, "TB99", 8, 4, 8)


def test_flagged_geometry_really_diverges():
    # a geometry the verifier rejects computes a wrong answer when it does
    # run — the flag is about real miscomputation, not style

    import jax

    from repro.kernels import ref

    sc = ConvScene(B=4, IC=8, OC=16, inH=6, inW=6, fltH=3, fltW=3)  # pad=0
    spec = mg.kernel_grid_spec(sc, "TB11", in_shape=sc.in_shape(),
                               flt_shape=sc.flt_shape())
    assert check_spec(spec) == []
    bad = dataclasses.replace(
        spec, out_index=lambda oh, ow, i, j: (0, ow, 0, 0))
    assert any(f.code == "out-overlap" for f in check_spec(bad))

    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    inp = jax.random.normal(k1, sc.in_shape(), jnp.float32)
    flt = jax.random.normal(k2, sc.flt_shape(), jnp.float32)
    got = mg._launch(bad, inp, flt, interpret=True)
    want = ref.conv_ref(inp, flt, sc)
    assert not np.allclose(np.asarray(got), np.asarray(want),
                           rtol=2e-4, atol=2e-4)


def test_verifier_vmem_agrees_with_selection_filter():
    # every point the tuner enumerates as feasible passes the verifier's
    # budget check, and an over-budget point is rejected by both
    for pt in tune_space.enumerate_space(STRIDED):
        fnd = verify_point(STRIDED, pt.schedule, pt.bm, pt.bn, pt.bk)
        assert not any(f.code == "vmem-overshoot" for f in fnd)
