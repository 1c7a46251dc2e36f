"""Quickstart: the MG3MConv public API in 40 lines — plan-once, execute-many.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.core.conv import ConvOp, ConvScene, make_plan
from repro.core.mapping import predicted_efficiency
from repro.kernels import ref

# 1. Describe the convolution scene (paper Table 1 symbols).
scene = ConvScene(B=32, IC=48, OC=64, inH=14, inW=14, fltH=3, fltW=3,
                  padH=1, padW=1)
print(scene.describe())

# 2. Build an execution plan ONCE: the multi-grained selector picks a TB
#    granularity (paper Fig. 14), and every padded/aligned shape is
#    precomputed into the frozen plan.
plan = make_plan(scene, ConvOp.FPROP)
choice = plan.choice
print(f"planned {choice.schedule} blocks=({choice.bm},{choice.bn},{choice.bk})"
      f" bound={choice.bound} "
      f"predicted MXU efficiency={predicted_efficiency(scene, choice):.1%}")

# 3. Execute MANY times — zero schedule resolutions, zero tune-cache IO,
#    zero shape arithmetic per call (interpret mode on CPU; native on TPU).
key = jax.random.PRNGKey(0)
inp = jax.random.normal(key, scene.in_shape(), jnp.float32)
flt = jax.random.normal(key, scene.flt_shape(), jnp.float32)
for _ in range(3):
    out = plan.execute(inp, flt)

# 4. Validate against the pure-jnp oracle.
want = ref.conv_ref(inp, flt, scene)
err = float(jnp.max(jnp.abs(out - want)))
print(f"output {out.shape}, max |err| vs oracle = {err:.2e}")
assert err < 1e-3

# 5. The backward directions are plans too: dL/dIN from a DGRAD plan.
d_in = make_plan(scene, ConvOp.DGRAD).execute(out, flt)
assert d_in.shape == scene.in_shape()
print("OK")
