"""ResNet-50 v1.5 on the plan path: the published scene table and
parameter count, and a reduced ResNet trained through
``build_cnn_train_step`` against the benchmark's plain reference
(``bench/references/resnet50.py``, which imports nothing of the program)."""
import math
import os
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.core.autodiff import make_model_plans
from repro.models import cnn as M
from repro.train import cnn as tc
from repro.train.optimizer import AdamWConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench.references import resnet50 as R  # noqa: E402


def test_resnet50_scene_table():
    sc = M.resnet_scenes(128)
    assert len(sc) == 53
    stem = sc["stem"]
    assert (stem.IC, stem.OC, stem.inH, stem.fltH, stem.padH, stem.stdH,
            stem.outH) == (3, 64, 224, 7, 3, 2, 112)
    hw = {1: 56, 2: 28, 3: 14, 4: 7}
    for i, (n, width) in enumerate(zip((3, 4, 6, 3), (64, 128, 256, 512)),
                                   start=1):
        for j in range(n):
            b = f"s{i}b{j}"
            std = 2 if i > 1 and j == 0 else 1
            in_hw = hw[i] * std
            ic = (64 if i == 1 else width * 2) if j == 0 else width * 4
            a, mid, c = sc[f"{b}.a"], sc[f"{b}.b"], sc[f"{b}.c"]
            assert (a.IC, a.OC, a.inH, a.fltH, a.stdH) == (ic, width, in_hw,
                                                           1, 1)
            assert (mid.IC, mid.OC, mid.inH, mid.fltH, mid.padH, mid.stdH,
                    mid.outH) == (width, width, in_hw, 3, 1, std, hw[i])
            assert (c.IC, c.OC, c.inH, c.fltH) == (width, 4 * width, hw[i], 1)
            if j == 0:
                p = sc[f"{b}.proj"]
                assert (p.IC, p.OC, p.inH, p.fltH, p.padH, p.stdH,
                        p.outH) == (ic, 4 * width, in_hw, 1, 0, std, hw[i])
            else:
                assert f"{b}.proj" not in sc
    assert {s.fltH for s in sc.values()} == {1, 3, 7}
    assert sum(s.fltH == 3 for s in sc.values()) == 16
    graph = M.resnet_graph()
    assert sum(isinstance(n, M.Bottleneck) for n in graph) == 16
    assert isinstance(graph[1], M.MaxPool) and graph[-1] == M.Head(bias=True)


def test_resnet50_parameter_count():
    """25,557,032 parameters in 161 tensors: 53 filters, 53 batch norms'
    gamma and beta, the head's weight and bias (torchvision's count)."""
    sc = M.resnet_scenes(1)
    shapes = jax.eval_shape(lambda k: M.init_resnet(k, sc),
                            jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(shapes)
    assert len(leaves) == 161
    assert sum(math.prod(x.shape) for x in leaves) == 25_557_032
    assert shapes["head"].shape == (2048, 1000)


BLOCKS = (1, 1, 1, 1)
B, RES = 4, 32
# Measured on the CPU over seeds 0-2 (program | the reference at three
# bf16 passes, one step below float32): logits 4.0e-6-6.5e-6 | 2.5e-4-
# 5.2e-4, loss 4.4e-7-1.5e-6 | 6.3e-6-1.3e-4, worst gradient 3.4e-5-
# 1.4e-4 | 1.9e-3-1.1e-1.  The gradients pass f32 rounding through 17 convs
# and batch norms whose statistics in stage 4 are over B x 1 x 1 = 4
# samples, so they amplify it most.  Each tolerance sits several times
# above the program and the logits and gradient ones below the control.
TOL = {"logits": 5e-5, "loss": 2e-5, "grads": 1e-3}


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.fixture(scope="module")
def reduced():
    """ResNet v1.5 with one block a stage, widths / 16, 32 px, B=4."""
    sc = M.resnet_scenes(B, RES, stem=4, widths=(4, 8, 16, 32),
                         blocks=BLOCKS)
    cfg = {"stages": [{"blocks": n} for n in BLOCKS], "bn_eps": M.BN_EPS,
           "layers": [{"name": n, "pad": s.padH, "stride": s.stdH}
                      for n, s in sc.items()]}
    p = M.init_resnet(jax.random.PRNGKey(0), sc, n_classes=10)
    x = jax.random.normal(jax.random.PRNGKey(10), (B, RES, RES, 3))
    y = jnp.arange(B) % 10
    return sc, cfg, p, x, y


def test_reduced_resnet_matches_reference(reduced):
    sc, cfg, p, x, y = reduced
    plans = make_model_plans(sc)
    assert plans.reference_ops == {}
    graph = M.resnet_graph(BLOCKS)
    step = tc.build_cnn_train_step(plans, AdamWConfig(), graph=graph,
                                   with_grads=True)
    _, out = jax.jit(step)(tc.init_train_state(p),
                           {"images": x, "labels": y})
    logits = jax.jit(lambda p: M.cnn_forward_planned(p, x, plans,
                                                     graph=graph))(p)
    want_logits = R.forward(cfg, p, x, "highest")
    want_loss, want_grads = jax.jit(
        lambda p: R.loss_and_grads(cfg, p, x, y, "highest"))(p)
    assert set(out["grads"]) == set(want_grads) == set(p)
    assert _rel(logits, want_logits) < TOL["logits"]
    assert abs(float(out["loss"]) - float(want_loss)) < (
        TOL["loss"] * float(want_loss))
    worst = max(_rel(out["grads"][k], want_grads[k]) for k in want_grads)
    assert worst < TOL["grads"]

    # the same comparison fails the reference one step below float32
    low_loss, low_grads = jax.jit(
        lambda p: R.loss_and_grads(cfg, p, x, y, "high"))(p)
    low = {"logits": _rel(R.forward(cfg, p, x, "high"), want_logits),
           "grads": max(_rel(low_grads[k], want_grads[k])
                        for k in want_grads)}
    assert all(low[k] > TOL[k] for k in low), low


def test_launcher_builds_resnet50():
    """``launch/train_cnn.py --model resnet50`` builds the 53-conv graph over
    Pallas plans in every direction (``--width`` scales every stage)."""
    import types

    from repro.launch.train_cnn import build_model
    args = types.SimpleNamespace(model="resnet50", batch=4, microbatches=1,
                                 sharded=False, seed=0, channels=3,
                                 classes=10, width=4, res=32,
                                 policy="analytic")
    params, plans, graph = build_model(args)
    assert graph == M.resnet_graph()
    assert len(plans) == 53 and plans.reference_ops == {}
    assert plans["s4b0.c"].scene.OC == 4 * 8 * 4
    assert params["head"].shape == (128, 10) and len(params) == 161
