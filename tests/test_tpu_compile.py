"""Compile-only checks for a described TPU v5e: the main-path kernels at
real widths, and a sharded model program over four chips.  Nothing runs,
so these guard what the chip's compiler accepts (tiling, VMEM, Mosaic
partitioning), not results.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers all
import this file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.autodiff import ModelPlans
from repro.core.mapping import DEVICE_COST_MODELS
from repro.core.scene import ConvScene
from repro.kernels import mg3m_conv as K
from repro.models.cnn import cnn_chain_scenes, cnn_scenes, resnet_scenes
from repro.plan import ConvOp, make_plan
from repro.plan.build import launched_shapes
from repro.shard import PARTITION_AXES, make_sharded_training_plans


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_call(plan):
    """The plan's kernel launch with the kernel mode forced to compile."""
    sc, spec = plan.exec_scene, plan.spec
    if spec.schedule == "WBL":
        return lambda a, b: K.wgrad_batch_lanes(a, b, sc, bn=spec.bn,
                                                bw=spec.bw, interpret=False)
    if spec.schedule == "TB11":
        return lambda a, b: K.conv_tb11(a, b, sc, bw=spec.bw,
                                        interpret=False)
    if spec.schedule == "TB18":
        return lambda a, b: K.conv_tb18(a, b, sc, bm=spec.bm, bw=spec.bw,
                                        interpret=False)
    return lambda a, b: K.conv_tb88(a, b, sc, bm=spec.bm, bn=spec.bn,
                                    bk=spec.bk, interpret=False)


def _resnet(layer, batch=8):
    return cnn_chain_scenes("resnet", batch)[f"resnet/L{layer}"]


def _conv3(b, ic, oc, hw, stride=1):
    """A 3x3, pad-1 layer of the benchmark's VGG-16 or All-CNN-C."""
    return ConvScene(B=b, IC=ic, OC=oc, inH=hw, inW=hw, fltH=3, fltW=3,
                     padH=1, padW=1, stdH=stride, stdW=stride)


# (case, scene, op, schedule the selector must pick, lhs-dilated exec scene,
#  strip width it must launch or None)
CASES = {
    "resnet_L0_fprop": (lambda: _resnet(0), ConvOp.FPROP, "TB11", False,
                        None),
    "resnet_L0_dgrad": (lambda: _resnet(0), ConvOp.DGRAD, "TB11", True, 1),
    "resnet_L0_wgrad": (lambda: _resnet(0), ConvOp.WGRAD, "WBL", False, 28),
    "resnet50_stem_wgrad_b128": (lambda: resnet_scenes(128)["stem"],
                                 ConvOp.WGRAD, "WBL", False, 28),
    "resnet_L9_fprop": (lambda: _resnet(9), ConvOp.FPROP, "TB18", False,
                        None),
    "alexnet_L0_b128": (lambda: cnn_scenes(128)["alexnet"][0],
                        ConvOp.FPROP, None, False, None),
    "vgg_L1_b128": (lambda: _conv3(128, 64, 64, 224), ConvOp.FPROP, "TB11",
                    False, 32),
    "vgg_L5_b128": (lambda: _conv3(128, 256, 256, 56), ConvOp.FPROP, "TB11",
                    False, 8),
    "vgg_L7_b128": (lambda: _conv3(128, 512, 512, 28), ConvOp.FPROP, "TB18",
                    False, 2),
    "allcnn_L2_b8": (lambda: _conv3(8, 96, 96, 32, stride=2), ConvOp.FPROP,
                     "TB11", False, 16),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    scene_fn, op, schedule, dilated, strip = CASES[case]
    plan = make_plan(scene_fn(), op)
    assert not plan.uses_reference
    if schedule:
        assert plan.schedule == schedule
    assert (plan.exec_scene.dilH > 1) == dilated
    if strip is not None:
        assert plan.spec.bw == strip
    shapes = launched_shapes(plan.exec_scene, plan.spec)
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(_kernel_call(plan)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_v5e_kind_has_published_peaks(topo):
    assert topo.devices[0].device_kind in DEVICE_COST_MODELS


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Steer the kernel mode to compile, with the trace caches cleared
    around it so no interpreter trace of the same plan is reused."""
    monkeypatch.setattr(K, "interpret_mode",
                        lambda interpret=None: interpret is True)
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_sharded_model_mixes_partitions_in_one_program(topo,
                                                       compiled_kernels):
    """A jitted model over four chips whose layers chose 4-way, 2-way and
    no partition: every plan must run on the whole pool, since a Mosaic
    kernel cannot be partitioned automatically."""
    ring = tuple(topo.devices)
    scenes = cnn_chain_scenes("resnet", 8, layers_per_net=3)
    axes = {"resnet/L0": PARTITION_AXES, "resnet/L1": (),
            "resnet/L2": PARTITION_AXES}
    plans = ModelPlans(layers=tuple(
        (name, make_sharded_training_plans(sc, devices=ring,
                                           axes=axes[name]))
        for name, sc in scenes.items()))
    tags = {t for name in plans for t in plans[name].shard_tags}
    assert {"none:1", "h:2", "h:4"} <= tags
    rep = NamedSharding(Mesh(np.asarray(ring), ("d",)), P())
    first = scenes["resnet/L0"]
    x = jax.ShapeDtypeStruct(first.in_shape(), jnp.float32, sharding=rep)
    ws = {n: jax.ShapeDtypeStruct(sc.flt_shape(), jnp.float32, sharding=rep)
          for n, sc in scenes.items()}

    def loss(ws, x):
        from repro.core.autodiff import apply_conv
        for name in plans:
            x = jax.nn.relu(apply_conv(x, ws[name], plans[name]))
        return x.sum()

    compiled = jax.jit(jax.grad(loss)).lower(ws, x).compile()
    assert "tpu_custom_call" in compiled.as_text()
