"""Pallas TPU kernels and their pure-jnp oracles (``ref``).

The kernel mode is derived from the platform here, in one place: the
kernels compile for the chip when JAX's default backend is a TPU and run
in the Pallas interpreter everywhere else.  Only the conv kernel
functions (``mg3m_conv.conv_tb11/18/88``) take an ``interpret`` argument,
so that tests can force a compile for a described chip.
"""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Whether a Pallas kernel runs in the interpreter: ``interpret`` when
    given (tests force a compile with False), else True exactly when the
    default backend is not a TPU."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"
