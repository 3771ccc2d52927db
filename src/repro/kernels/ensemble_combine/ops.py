"""Jit'd public op for ensemble combine: computes the eq.-(5) mixture
weights in stable log space, then dispatches the Pallas kernel (interpret
mode on CPU; compiled on TPU)."""

from __future__ import annotations

import jax.numpy as jnp

from ..platform import interpret_mode
from .kernel import ensemble_combine_pallas
from .ref import mix_weights_ref

__all__ = ["ensemble_combine"]


def ensemble_combine(preds: jnp.ndarray, log_w: jnp.ndarray,
                     sel: jnp.ndarray) -> jnp.ndarray:
    """preds: (K, N); log_w/sel: (K,) -> ensemble predictions (N,)."""
    mix = mix_weights_ref(log_w, sel)
    return ensemble_combine_pallas(preds, mix, interpret=interpret_mode())
