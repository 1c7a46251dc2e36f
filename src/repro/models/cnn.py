"""CNN zoo for the paper's own evaluation (Fig. 13): AlexNet, VGG, GoogLeNet,
ResNet, SqueezeNet, YOLO — as lists of convolution *scenes* (the paper
benchmarks per-layer conv hardware efficiency, not end-to-end accuracy),
plus runnable trainable classifiers (a small 3-conv CNN, a scenes-backed
VGG-style net and ResNet-50 v1.5 at its published widths) whose every
convolution dispatches through prewarmed ``ConvPlan`` triples.

A trainable net is a *layer graph* (``Conv``, ``MaxPool``, ``Bottleneck``,
``Head`` nodes) walked by one forward, ``cnn_forward_planned``; a relu
chain of convs is the graph's linear case (``chain_graph``).

Layout discipline: the plan path converts NHWC to the paper's plan layout
``[H, W, C, B]`` exactly once at model entry and back never — relu, batch
norm, max-pool, the residual adds, the global average pool and the head
all speak plan layout — so a forward or training step performs zero
per-layer transposes.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.scene import ConvScene
from repro.kernels.ref import conv_ref

Params = Dict[str, jax.Array]


def trunc_normal(key, shape, std, dtype):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def nhwc_to_plan(x: jax.Array) -> jax.Array:
    """NHWC -> plan layout [H, W, C, B] (the paper's IN layout) — the one
    entry transpose of the plan-driven model path."""
    return jnp.transpose(x, (1, 2, 3, 0))


def plan_to_nhwc(x: jax.Array) -> jax.Array:
    """Plan layout [H, W, C, B] -> NHWC — the matching exit transpose (the
    classifier heads below never need it: they pool in plan layout)."""
    return jnp.transpose(x, (3, 0, 1, 2))


def _s(b, ic, oc, hw, f, pad, std, in_hw=None) -> ConvScene:
    return ConvScene(B=b, IC=ic, OC=oc, inH=in_hw or hw, inW=in_hw or hw,
                     fltH=f, fltW=f, padH=pad, padW=pad, stdH=std, stdW=std)


def cnn_scenes(batch: int = 128) -> Dict[str, List[ConvScene]]:
    """Representative conv layers of the six CNNs (paper Fig. 13 workload).

    Channel/spatial configs from the original architectures; batch follows
    the paper's batch-number experiments.
    """
    b = batch
    return {
        "alexnet": [
            _s(b, 3, 64, 224, 11, 2, 4), _s(b, 64, 192, 27, 5, 2, 1),
            _s(b, 192, 384, 13, 3, 1, 1), _s(b, 384, 256, 13, 3, 1, 1),
            _s(b, 256, 256, 13, 3, 1, 1),
        ],
        "vgg": [
            _s(b, 3, 64, 224, 3, 1, 1), _s(b, 64, 64, 224, 3, 1, 1),
            _s(b, 64, 128, 112, 3, 1, 1), _s(b, 128, 128, 112, 3, 1, 1),
            _s(b, 128, 256, 56, 3, 1, 1), _s(b, 256, 256, 56, 3, 1, 1),
            _s(b, 256, 512, 28, 3, 1, 1), _s(b, 512, 512, 28, 3, 1, 1),
            _s(b, 512, 512, 14, 3, 1, 1),
        ],
        "googlenet": [
            _s(b, 3, 64, 224, 7, 3, 2), _s(b, 64, 192, 56, 3, 1, 1),
            _s(b, 192, 96, 28, 1, 0, 1), _s(b, 96, 128, 28, 3, 1, 1),
            _s(b, 16, 32, 28, 5, 2, 1),   # inception 3a/5x5 (paper's example)
            _s(b, 480, 192, 14, 1, 0, 1), _s(b, 112, 224, 14, 3, 1, 1),
        ],
        "resnet": [
            _s(b, 3, 64, 224, 7, 3, 2), _s(b, 64, 64, 56, 1, 0, 1),
            _s(b, 64, 64, 56, 3, 1, 1), _s(b, 64, 256, 56, 1, 0, 1),
            _s(b, 256, 128, 56, 1, 0, 2), _s(b, 128, 128, 28, 3, 1, 1),
            _s(b, 512, 256, 28, 1, 0, 2), _s(b, 256, 256, 14, 3, 1, 1),
            _s(b, 1024, 512, 14, 1, 0, 2), _s(b, 512, 512, 7, 3, 1, 1),
        ],
        "squeezenet": [
            _s(b, 3, 96, 224, 7, 2, 2), _s(b, 96, 16, 55, 1, 0, 1),
            _s(b, 16, 64, 55, 1, 0, 1), _s(b, 16, 64, 55, 3, 1, 1),
            _s(b, 128, 32, 27, 1, 0, 1), _s(b, 32, 128, 27, 3, 1, 1),
            _s(b, 256, 48, 13, 1, 0, 1), _s(b, 48, 192, 13, 3, 1, 1),
        ],
        "yolo": [
            _s(b, 3, 16, 448, 3, 1, 1), _s(b, 16, 32, 224, 3, 1, 1),
            _s(b, 32, 64, 112, 3, 1, 1), _s(b, 64, 128, 56, 3, 1, 1),
            _s(b, 128, 256, 28, 3, 1, 1), _s(b, 256, 512, 14, 3, 1, 1),
            _s(b, 512, 1024, 7, 3, 1, 1),
        ],
    }


def cnn_layer_scenes(nets=None, batch: int = 1, *,
                     max_hw: int = 0, max_ch: int = 0,
                     layers_per_net: int = 0) -> Dict[str, ConvScene]:
    """Flat ``{"net/L<i>": scene}`` over the paper CNNs — the serving
    layer list (``repro.serve.conv`` prewarms straight from it).

    ``max_hw``/``max_ch`` cap spatial/channel dims via the tune subsystem's
    proxy convention (``tune.measure.proxy_scene``): the cap keeps the
    filter window valid and preserves each layer's stride/pad/remainder
    structure, so interpret-mode CPU serving demos and CI bursts stay
    feasible while still exercising the awkward layers (AlexNet's 11x11/s4
    remainder entry, the 7x7/s2 stems, pointwise projections).  0 = full
    paper scenes.  ``layers_per_net`` truncates each net's list (0 = all).
    """
    all_scenes = cnn_scenes(batch)
    nets = tuple(all_scenes) if nets is None else tuple(nets)
    out: Dict[str, ConvScene] = {}
    for net in nets:
        if net not in all_scenes:
            raise KeyError(f"unknown net {net!r}; have {sorted(all_scenes)}")
        layers = all_scenes[net]
        if layers_per_net:
            layers = layers[:layers_per_net]
        for i, sc in enumerate(layers):
            if max_hw or max_ch:
                # the tune proxy already knows how to shrink a scene while
                # keeping the filter window valid — reuse it, lazily so the
                # uncapped path never touches the tune subsystem
                from repro.tune.measure import proxy_scene
                sc = proxy_scene(sc, measure_max_ch=max_ch or None,
                                 measure_max_hw=max_hw or None)
            out[f"{net}/L{i}"] = sc
    return out


def cnn_chain_scenes(net: str, batch: int = 1, *,
                     max_hw: int = 0, max_ch: int = 0,
                     layers_per_net: int = 0) -> Dict[str, ConvScene]:
    """A *chained* ``{"net/L<i>": scene}`` conv trunk for one paper CNN —
    the whole-model serving input (``repro.serve.sched.register_net``).

    ``cnn_scenes`` lists each net's representative conv layers with the
    pooling between them elided, so consecutive scenes do not chain (layer
    i's output geometry is not layer i+1's input).  A whole-model session
    needs a valid chain (``validate_scene_chain``), so this keeps each
    layer's filter/stride/pad/OC character but forces its input geometry to
    the previous layer's output — the inter-layer pooling is folded into
    the conv stride chain, the way ``vgg_style_scenes`` replaces pooling
    with stride-2 convs.

    ``max_hw``/``max_ch`` caps are applied *during* construction, not after:
    capping a finished chain layer-by-layer (the ``proxy_scene`` route)
    would break the OC -> IC / out -> in couplings.  Filters clamp to the
    running spatial size (``f = min(flt, hw)``) and padding to ``f - 1`` so
    every window stays valid however small the trunk gets.
    """
    all_scenes = cnn_scenes(batch)
    if net not in all_scenes:
        raise KeyError(f"unknown net {net!r}; have {sorted(all_scenes)}")
    base = all_scenes[net]
    if layers_per_net:
        base = base[:layers_per_net]
    out: Dict[str, ConvScene] = {}
    hw = min(base[0].inH, max_hw) if max_hw else base[0].inH
    ic = min(base[0].IC, max_ch) if max_ch else base[0].IC
    for i, sc in enumerate(base):
        oc = min(sc.OC, max_ch) if max_ch else sc.OC
        f = min(sc.fltH, hw)
        pad = min(sc.padH, f - 1) if f > 1 else 0
        chained = ConvScene(B=batch, IC=ic, OC=oc, inH=hw, inW=hw,
                            fltH=f, fltW=f, padH=pad, padW=pad,
                            stdH=sc.stdH, stdW=sc.stdW, dtype=sc.dtype)
        out[f"{net}/L{i}"] = chained
        hw, ic = chained.outH, oc
    validate_scene_chain(out)
    return out


# ---------------------------------------------------------------------------
# Small runnable classifier on MG3MConv (end-to-end example / tests)
# ---------------------------------------------------------------------------
def init_small_cnn(key, *, in_ch: int = 3, n_classes: int = 10,
                   width: int = 16, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 4)
    return {
        "c1": trunc_normal(ks[0], (3, 3, in_ch, width), 0.1, dtype),
        "c2": trunc_normal(ks[1], (3, 3, width, width * 2), 0.05, dtype),
        "c3": trunc_normal(ks[2], (3, 3, width * 2, width * 4), 0.05, dtype),
        "head": trunc_normal(ks[3], (width * 4, n_classes), 0.05, dtype),
    }


_LAYER_STRIDES = {"c1": 1, "c2": 2, "c3": 2}


def small_cnn_scenes(p: Params, batch: int, res: int,
                     dtype: str = "float32") -> Dict[str, ConvScene]:
    """Per-layer ConvScenes of the small CNN for a given input geometry."""
    scenes = {}
    hw = res
    for name, stride in _LAYER_STRIDES.items():
        w = p[name]
        scenes[name] = ConvScene(B=batch, IC=w.shape[2], OC=w.shape[3],
                                 inH=hw, inW=hw, fltH=w.shape[0],
                                 fltW=w.shape[1], padH=1, padW=1,
                                 stdH=stride, stdW=stride, dtype=dtype)
        hw = scenes[name].outH
    return scenes


def small_cnn_plans(p: Params, batch: int, res: int, *,
                    dtype: str = "float32", policy=None,
                    devices=None) -> "ModelPlans":
    """Pre-build the (fprop, dgrad, wgrad) plan triple of every layer into
    one ``ModelPlans`` — plan-once (one ``PlanRegistry.warm`` pass), then
    every forward/backward step is pure dispatch.  ``devices`` (a
    data-parallel ring) builds mesh-sharded triples instead."""
    from repro.core.autodiff import make_model_plans
    return make_model_plans(small_cnn_scenes(p, batch, res, dtype),
                            policy=policy, devices=devices)


def small_cnn_forward(p: Params, x: jax.Array, *, schedule=None,
                      plans=None) -> jax.Array:
    """x: [B, H, W, C] -> logits [B, n_classes].  All convs via MG3MConv.

    Runs the differentiable plan path (``core/autodiff.apply_conv``), so the
    whole CNN trains through the Pallas forward; the activation enters plan
    layout once and stays there across c1 -> c2 -> c3 -> pool -> head (no
    per-layer transposes).  Pass ``plans`` (from ``small_cnn_plans``) to
    use pre-built per-layer plans; otherwise they are built here under the
    ``schedule`` policy.
    """
    if plans is None:
        plans = small_cnn_plans(p, x.shape[0], x.shape[1],
                                dtype=str(x.dtype), policy=schedule)
    return cnn_forward_planned(p, x, plans,
                               graph=chain_graph(tuple(_LAYER_STRIDES)))


def small_cnn_reference(p: Params, x: jax.Array) -> jax.Array:
    """The plain reference of ``small_cnn_forward``: the same three convs,
    ReLU, mean-pool and head, each conv on ``kernels/ref.conv_ref`` (lax at
    ``HIGHEST``) in plan layout."""
    z = nhwc_to_plan(x)
    for name, stride in _LAYER_STRIDES.items():
        sc = ConvScene(B=z.shape[3], IC=z.shape[2], OC=p[name].shape[3],
                       inH=z.shape[0], inW=z.shape[1], fltH=p[name].shape[0],
                       fltW=p[name].shape[1], padH=1, padW=1, stdH=stride,
                       stdW=stride, dtype=str(x.dtype))
        z = jax.nn.relu(conv_ref(z, p[name], sc))
    return z.mean(axis=(0, 1)).T @ p["head"]


# ---------------------------------------------------------------------------
# Layer graphs and the one plan-layout forward that walks them
# ---------------------------------------------------------------------------
BN_EPS = 1e-5


@dataclasses.dataclass(frozen=True)
class Conv:
    """Conv ``name`` through its plan triple, then training-mode batch norm
    (params ``<name>.gamma``/``<name>.beta``) when ``bn``, then ReLU when
    ``relu``."""

    name: str
    bn: bool = False
    relu: bool = True


@dataclasses.dataclass(frozen=True)
class MaxPool:
    """``window``x``window`` max-pool, stride ``stride``, ``pad`` rows and
    columns of -inf on every side."""

    window: int = 3
    stride: int = 2
    pad: int = 1


@dataclasses.dataclass(frozen=True)
class Bottleneck:
    """Residual block: ``convs`` in sequence, ``proj`` (or the identity) on
    the block's input, their sum, then ReLU."""

    name: str
    convs: Tuple[Conv, ...]
    proj: Optional[Conv] = None


@dataclasses.dataclass(frozen=True)
class Head:
    """Global average pool, then the linear classifier ``head`` (plus the
    bias ``head_b`` when ``bias``)."""

    bias: bool = False


Node = Union[Conv, MaxPool, Bottleneck, Head]


def chain_graph(names: Sequence[str]) -> Tuple[Node, ...]:
    """The relu chain of convs ``names`` and a bias-free head: the graph
    of ``small_cnn`` and the scenes-backed nets."""
    return tuple(Conv(n) for n in names) + (Head(),)


def batch_norm(z: jax.Array, gamma: jax.Array, beta: jax.Array,
               eps: float = BN_EPS) -> jax.Array:
    """Training-mode batch norm in plan layout ``[H, W, C, B]``: per-channel
    mean and biased variance over H, W and B (no running statistics)."""
    axes = (0, 1, 3)
    mean = z.mean(axis=axes, keepdims=True)
    centred = z - mean
    var = jnp.square(centred).mean(axis=axes, keepdims=True)
    scale = gamma.reshape(1, 1, -1, 1) * jax.lax.rsqrt(var + eps)
    return centred * scale + beta.reshape(1, 1, -1, 1)


def max_pool(z: jax.Array, window: int = 3, stride: int = 2,
             pad: int = 1) -> jax.Array:
    """Spatial max-pool in plan layout with -inf padding."""
    return jax.lax.reduce_window(
        z, -jnp.inf, jax.lax.max,
        (window, window, 1, 1), (stride, stride, 1, 1),
        ((pad, pad), (pad, pad), (0, 0), (0, 0)))


def _walk(node: Node, p: Params, z: jax.Array, plans) -> jax.Array:
    """Apply one graph node to a plan-layout activation.  Non-conv ops sit
    under ``repro.graph.{bn,pool,add,head}`` named scopes, each block
    under ``repro.graph.<block>``, so a device trace can attribute them."""
    from repro.core.autodiff import apply_conv
    if isinstance(node, Conv):
        z = apply_conv(z, p[node.name], plans[node.name])
        if node.bn:
            with jax.named_scope("repro.graph.bn"):
                z = batch_norm(z, p[node.name + ".gamma"],
                               p[node.name + ".beta"])
                return jax.nn.relu(z) if node.relu else z
        return jax.nn.relu(z) if node.relu else z
    if isinstance(node, MaxPool):
        with jax.named_scope("repro.graph.pool"):
            return max_pool(z, node.window, node.stride, node.pad)
    if isinstance(node, Bottleneck):
        with jax.named_scope(f"repro.graph.{node.name}"):
            y = z
            for conv in node.convs:
                y = _walk(conv, p, y, plans)
            short = z if node.proj is None else _walk(node.proj, p, z, plans)
            with jax.named_scope("repro.graph.add"):
                return jax.nn.relu(y + short)
    if isinstance(node, Head):
        with jax.named_scope("repro.graph.head"):
            pooled = z.mean(axis=(0, 1))              # [C, B], plan layout
            logits = jnp.dot(pooled.T, p["head"],
                             precision=jax.lax.Precision.HIGHEST)
            return logits + p["head_b"] if node.bias else logits
    raise TypeError(f"unknown graph node {node!r}")


def cnn_forward_planned(p: Params, x: jax.Array, plans,
                        graph: Optional[Sequence[Node]] = None) -> jax.Array:
    """Plan-layout forward shared by every trainable CNN here: one NHWC ->
    [H,W,C,B] transpose at entry, then the layer ``graph`` walked with the
    activation held in plan layout, ending in its ``Head``.

    ``plans`` is a ``ModelPlans`` (or any name -> triple mapping).  Without
    ``graph`` the net is the relu chain of the plans' layers in their own
    order (``chain_graph``).
    """
    nodes = graph if graph is not None else chain_graph(tuple(plans))
    z = nhwc_to_plan(x)
    for node in nodes:
        z = _walk(node, p, z, plans)
    return z


# ---------------------------------------------------------------------------
# ResNet v1.5 (He et al., arXiv:1512.03385, Table 1; stride on the 3x3)
# ---------------------------------------------------------------------------
RESNET50_BLOCKS = (3, 4, 6, 3)
RESNET50_WIDTHS = (64, 128, 256, 512)


def resnet_scenes(batch: int, res: int = 224, *, in_ch: int = 3,
                  stem: int = 64,
                  widths: Sequence[int] = RESNET50_WIDTHS,
                  blocks: Sequence[int] = RESNET50_BLOCKS,
                  expansion: int = 4,
                  dtype: str = "float32") -> Dict[str, ConvScene]:
    """Every conv scene of a bottleneck ResNet v1.5, in forward order:
    the 7x7/2 ``stem`` (then a 3x3/2 max-pool), and per stage ``i`` (from
    1) and block ``j`` (from 0) the 1x1 ``s<i>b<j>.a``, the 3x3
    ``s<i>b<j>.b`` (stride 2 in the first block of stages 2-4), the 1x1
    ``s<i>b<j>.c`` to ``expansion`` x the width, and in each stage's first
    block the 1x1 projection ``s<i>b<j>.proj`` (strided as ``.b``).  The
    defaults are ResNet-50: 53 convs."""
    scenes: Dict[str, ConvScene] = {}

    def add(name, ic, oc, hw, f, pad, std):
        scenes[name] = ConvScene(B=batch, IC=ic, OC=oc, inH=hw, inW=hw,
                                 fltH=f, fltW=f, padH=pad, padW=pad,
                                 stdH=std, stdW=std, dtype=dtype)
        return scenes[name].outH

    hw = add("stem", in_ch, stem, res, 7, 3, 2)
    hw = (hw + 2 - 3) // 2 + 1                       # the 3x3/2 max-pool
    ic = stem
    for i, (n, width) in enumerate(zip(blocks, widths), start=1):
        for j in range(n):
            name, std = f"s{i}b{j}", 2 if i > 1 and j == 0 else 1
            add(f"{name}.a", ic, width, hw, 1, 0, 1)
            out_hw = add(f"{name}.b", width, width, hw, 3, 1, std)
            add(f"{name}.c", width, width * expansion, out_hw, 1, 0, 1)
            if j == 0:
                add(f"{name}.proj", ic, width * expansion, hw, 1, 0, std)
            hw, ic = out_hw, width * expansion
    return scenes


def resnet_graph(blocks: Sequence[int] = RESNET50_BLOCKS
                 ) -> Tuple[Node, ...]:
    """The layer graph over ``resnet_scenes``' names: stem conv + BN +
    ReLU, max-pool, the bottleneck blocks (BN after every conv, no ReLU
    before the residual add), and the biased head."""
    nodes: List[Node] = [Conv("stem", bn=True), MaxPool()]
    for i, n in enumerate(blocks, start=1):
        for j in range(n):
            b = f"s{i}b{j}"
            nodes.append(Bottleneck(
                b, (Conv(f"{b}.a", bn=True), Conv(f"{b}.b", bn=True),
                    Conv(f"{b}.c", bn=True, relu=False)),
                Conv(f"{b}.proj", bn=True, relu=False) if j == 0 else None))
    nodes.append(Head(bias=True))
    return tuple(nodes)


def init_resnet(key, scenes: Mapping[str, ConvScene], n_classes: int = 1000,
                dtype=jnp.float32) -> Params:
    """ResNet parameters as torchvision initialises them: He-normal filters
    (fan-out, ``FLT[h,w,IC,OC]``), BN ``gamma`` 1 and ``beta`` 0, and a
    head (``head`` ``[C, n_classes]``, ``head_b``) uniform in
    +-1/sqrt(C)."""
    items = list(scenes.items())
    ks = jax.random.split(key, len(items) + 2)
    p: Params = {}
    for k, (name, sc) in zip(ks, items):
        std = (2.0 / (sc.fltH * sc.fltW * sc.OC)) ** 0.5
        p[name] = (jax.random.normal(k, sc.flt_shape(), jnp.float32)
                   * std).astype(dtype)
        p[name + ".gamma"] = jnp.ones((sc.OC,), dtype)
        p[name + ".beta"] = jnp.zeros((sc.OC,), dtype)
    width = items[-1][1].OC
    bound = width ** -0.5
    p["head"] = jax.random.uniform(ks[-2], (width, n_classes), dtype,
                                   -bound, bound)
    p["head_b"] = jax.random.uniform(ks[-1], (n_classes,), dtype,
                                     -bound, bound)
    return p


# ---------------------------------------------------------------------------
# Scenes-backed trainable CNN (VGG-style): the scene chain IS the model
# ---------------------------------------------------------------------------
def vgg_style_scenes(batch: int, res: int = 16, in_ch: int = 3,
                     stages: Sequence[Tuple[int, int]] = ((16, 1), (32, 2),
                                                          (64, 2)),
                     dtype: str = "float32") -> Dict[str, ConvScene]:
    """A chained VGG-style scene list: 3x3 pad-1 convs, widths and strides
    from ``stages`` (stride-2 convs in place of pooling).  The returned
    dict is a valid ``init_cnn_from_scenes``/``make_model_plans`` input."""
    scenes: Dict[str, ConvScene] = {}
    hw, ic = res, in_ch
    for i, (width, stride) in enumerate(stages):
        sc = ConvScene(B=batch, IC=ic, OC=width, inH=hw, inW=hw,
                       fltH=3, fltW=3, padH=1, padW=1,
                       stdH=stride, stdW=stride, dtype=dtype)
        scenes[f"v{i}"] = sc
        hw, ic = sc.outH, width
    return scenes


def validate_scene_chain(scenes: Mapping[str, ConvScene]) -> None:
    """Raise ``ValueError`` unless consecutive scenes chain: layer i's
    output channels and spatial dims must be layer i+1's input."""
    if not scenes:
        raise ValueError("a scenes-backed CNN needs at least one conv scene")
    items = list(scenes.items())
    for (na, a), (nb, b) in zip(items, items[1:]):
        if a.OC != b.IC:
            raise ValueError(f"scene chain breaks at {na} -> {nb}: "
                             f"OC={a.OC} feeds IC={b.IC}")
        if (a.outH, a.outW) != (b.inH, b.inW):
            raise ValueError(f"scene chain breaks at {na} -> {nb}: output "
                             f"{a.outH}x{a.outW} feeds input "
                             f"{b.inH}x{b.inW}")
        if a.B != b.B:
            raise ValueError(f"scene chain breaks at {na} -> {nb}: "
                             f"batch {a.B} vs {b.B}")


def init_cnn_from_scenes(key, scenes: Mapping[str, ConvScene],
                         n_classes: int = 10, dtype=jnp.float32) -> Params:
    """Parameters of the scenes-backed CNN: one FLT[h,w,IC,OC] per scene
    (paper layout — no transpose between init and plan execution) plus the
    linear head off the global average pool."""
    validate_scene_chain(scenes)
    items = list(scenes.items())
    ks = jax.random.split(key, len(items) + 1)
    p: Params = {}
    for k, (name, sc) in zip(ks, items):
        std = 0.1 if sc.IC <= 4 else (2.0 / (sc.fltH * sc.fltW
                                             * sc.IC)) ** 0.5
        p[name] = trunc_normal(k, (sc.fltH, sc.fltW, sc.IC, sc.OC),
                               std, dtype)
    p["head"] = trunc_normal(ks[-1], (items[-1][1].OC, n_classes),
                             0.05, dtype)
    return p
