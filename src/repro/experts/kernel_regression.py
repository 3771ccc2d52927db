"""Kernel ridge regressors — the paper's pre-trained expert pool members.

Each expert is fit (closed form) on a 10% split of the dataset: anchors
``A`` (m, d) and coefficients ``alpha = (K(A, A) + lam I)^{-1} y``.
Prediction is ``k(x, A) @ alpha`` — the client-side compute hotspot, which
is what `repro.kernels.kernel_gram` accelerates (this module's `predict`
routes through it).

The pool (`repro.experts.pool`) fits and predicts at full float32 matmul
precision; see there.

Kernel families (paper §IV):
  gaussian   exp(-gamma ||x - a||^2)        gamma in {0.01, 0.1, 1, 10, 100}
  laplacian  exp(-gamma ||x - a||_1)        same gammas
  polynomial (x . a + 1)^degree             degree in {1..5}
  sigmoid    tanh(slope * x . a + 1)        slope in {0.01, 0.1, 1, 10, 100}
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["KernelExpert", "fit_kernel_expert", "kernel_matrix", "predict"]

KERNELS = ("gaussian", "laplacian", "polynomial", "sigmoid")


class KernelExpert(NamedTuple):
    kind: str
    param: float          # gamma / degree / slope
    anchors: jnp.ndarray  # (m, d)
    alpha: jnp.ndarray    # (m,)
    n_params: int         # for the cost model: anchors*d + m coefficients


def kernel_matrix(kind: str, param: float, x: jnp.ndarray,
                  a: jnp.ndarray) -> jnp.ndarray:
    """K(x, a): (n, m).  Pure jnp — also the oracle for the Pallas kernel."""
    if kind == "gaussian":
        sq = (jnp.sum(x * x, 1)[:, None] - 2.0 * x @ a.T
              + jnp.sum(a * a, 1)[None, :])
        return jnp.exp(-param * jnp.maximum(sq, 0.0))
    if kind == "laplacian":
        l1 = jnp.sum(jnp.abs(x[:, None, :] - a[None, :, :]), axis=-1)
        return jnp.exp(-param * l1)
    if kind == "polynomial":
        return (x @ a.T + 1.0) ** param
    if kind == "sigmoid":
        return jnp.tanh(param * (x @ a.T) + 1.0)
    raise ValueError(f"unknown kernel {kind!r}")


def fit_kernel_expert(kind: str, param: float, x_train: np.ndarray,
                      y_train: np.ndarray, lam: float = 1e-3) -> KernelExpert:
    """Closed-form kernel ridge fit on the pre-training split."""
    x = jnp.asarray(x_train, jnp.float32)
    y = jnp.asarray(y_train, jnp.float32)
    m = x.shape[0]
    gram = kernel_matrix(kind, param, x, x)
    alpha = jnp.linalg.solve(gram + lam * jnp.eye(m, dtype=gram.dtype), y)
    n_params = int(m * x.shape[1] + m)
    return KernelExpert(kind, float(param), x, alpha, n_params)


def predict(expert: KernelExpert, x: jnp.ndarray,
            use_pallas: bool = True) -> jnp.ndarray:
    """y_hat(x) = K(x, anchors) @ alpha, via the Pallas kernel_gram op for
    the MXU-friendly families when available."""
    if use_pallas and expert.kind in ("gaussian", "polynomial", "sigmoid"):
        from repro.kernels.kernel_gram import ops as kg_ops
        return kg_ops.kernel_predict(expert.kind, expert.param, x,
                                     expert.anchors, expert.alpha)
    return kernel_matrix(expert.kind, expert.param, x, expert.anchors) @ expert.alpha
