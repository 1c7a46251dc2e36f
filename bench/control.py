"""The control of the comparison that decides ``correct``: the plain
reference one step below the precision a configuration states (three bf16
passes for float32, ``"high"`` in ``references/``) planted in the
program's place, under ``ConvPlan.execute``, so that a whole run of a cell
goes through its own loop and its own judgement with it.  Such a run has
to come out ``correct: false``.

    with planted(bench.reference(config["reference"])):
        line = run.run_cell(...)
"""
from __future__ import annotations

import contextlib

PRECISION = "high"


@contextlib.contextmanager
def planted(reference):
    """Every forward ``ConvPlan.execute`` in the block returns the
    reference's convolution at ``PRECISION`` of the plan's own scene."""
    import jax

    from repro.plan.build import ConvOp, ConvPlan

    fns = {}

    def execute(self, a, b):
        sc = self.scene
        if self.op is not ConvOp.FPROP:
            raise NotImplementedError("the control covers forward plans")
        key = (sc.padH, sc.stdH)
        if key not in fns:
            layer = {"pad": sc.padH, "stride": sc.stdH}
            fns[key] = jax.jit(lambda x, w, layer=layer: reference.conv(
                x, w, layer, PRECISION))
        return fns[key](a, b)

    orig = ConvPlan.execute, ConvPlan.__call__
    ConvPlan.execute = ConvPlan.__call__ = execute
    try:
        yield
    finally:
        ConvPlan.execute, ConvPlan.__call__ = orig
