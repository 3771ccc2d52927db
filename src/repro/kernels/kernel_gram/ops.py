"""Jit'd public op for kernel-regression prediction: Pallas on the
MXU-friendly families (compiled on TPU, interpret mode on CPU)."""

from __future__ import annotations

import jax.numpy as jnp

from ..platform import interpret_mode
from .kernel import kernel_predict_pallas
from .ref import kernel_predict_ref, SUPPORTED

__all__ = ["kernel_predict", "SUPPORTED"]


def kernel_predict(kind: str, param: float, x, anchors, alpha):
    if kind not in SUPPORTED:
        raise ValueError(f"{kind!r} has no Pallas path (use the jnp ref)")
    interpret = interpret_mode()
    return kernel_predict_pallas(kind, float(param), jnp.asarray(x),
                                 jnp.asarray(anchors), jnp.asarray(alpha),
                                 interpret=interpret)
