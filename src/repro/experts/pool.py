"""The paper's 22-model pre-trained expert pool (§IV).

  5 Gaussian kernels   gamma  in {0.01, 0.1, 1, 10, 100}
  5 Laplacian kernels  gamma  in {0.01, 0.1, 1, 10, 100}
  5 polynomial kernels degree in {1, 2, 3, 4, 5}
  5 sigmoid kernels    slope  in {0.01, 0.1, 1, 10, 100}
  2 MLPs               1 / 2 hidden layers x 25 ReLU units

Every expert is pre-trained on the same 10% split ("pre-trained models can
be trained on publicly available data without observing clients' data").
Transmission cost c_k = n_params_k / max_k n_params_k, so max cost = 1
(paper §IV), and the budget is B = 3.

Both entry points fit and predict at full float32 matmul precision
(``HIGHEST``): a TPU's default rounds float32 matmul operands to bfloat16,
which the ill-conditioned ridge solve and the large-coefficient kernel
predictions amplify far past float32 rounding, and which the MLPs'
contractions carry too.  On the CPU float32 matmuls are exact to the dtype
already, so the setting changes nothing there.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from .kernel_regression import KernelExpert, fit_kernel_expert, predict as kr_predict
from .mlp import MLPExpert, fit_mlp_expert, mlp_apply

__all__ = ["ExpertPool", "build_paper_pool", "pool_predict_all"]

GAMMAS = (0.01, 0.1, 1.0, 10.0, 100.0)
DEGREES = (1.0, 2.0, 3.0, 4.0, 5.0)


def _full_f32(fn):
    """Run ``fn`` with float32 matmuls at ``HIGHEST`` precision."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return wrapped


class ExpertPool(NamedTuple):
    experts: tuple                 # KernelExpert | MLPExpert, length K
    names: tuple                   # str labels
    costs: jnp.ndarray             # (K,) normalized transmission costs


@_full_f32
def build_paper_pool(x_pre: np.ndarray, y_pre: np.ndarray,
                     seed: int = 0,
                     subsample_anchors: int | None = None) -> ExpertPool:
    """Fit the 22 experts on the pre-training split (10% of the dataset).

    ``subsample_anchors`` caps the kernel-ridge anchor count (the closed
    form is O(m^3)); the paper does not cap, but for the largest dataset
    (Energy, m=1973) an uncapped solve is still fine on CPU — the cap
    exists for fast unit tests.
    """
    rng = np.random.default_rng(seed)
    if subsample_anchors is not None and x_pre.shape[0] > subsample_anchors:
        idx = rng.choice(x_pre.shape[0], subsample_anchors, replace=False)
        x_pre, y_pre = x_pre[idx], y_pre[idx]

    experts, names = [], []
    for g in GAMMAS:
        experts.append(fit_kernel_expert("gaussian", g, x_pre, y_pre))
        names.append(f"gaussian[{g}]")
    for g in GAMMAS:
        experts.append(fit_kernel_expert("laplacian", g, x_pre, y_pre))
        names.append(f"laplacian[{g}]")
    for d in DEGREES:
        experts.append(fit_kernel_expert("polynomial", d, x_pre, y_pre))
        names.append(f"poly[{int(d)}]")
    for g in GAMMAS:
        experts.append(fit_kernel_expert("sigmoid", g, x_pre, y_pre))
        names.append(f"sigmoid[{g}]")
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    experts.append(fit_mlp_expert(k1, x_pre, y_pre, hidden_layers=1))
    names.append("mlp[1x25]")
    experts.append(fit_mlp_expert(k2, x_pre, y_pre, hidden_layers=2))
    names.append("mlp[2x25]")

    n_params = np.array([e.n_params for e in experts], dtype=np.float64)
    costs = jnp.asarray(n_params / n_params.max(), jnp.float32)
    return ExpertPool(tuple(experts), tuple(names), costs)


@_full_f32
def pool_predict_all(pool: ExpertPool, x: np.ndarray,
                     use_pallas: bool = False,
                     clip: float | None = 5.0) -> jnp.ndarray:
    """(K, n) matrix of every expert's prediction on ``x``.

    Benchmarks precompute this once per dataset — the federated round then
    only indexes client columns, which keeps thousand-round simulations
    fast while preserving exact per-round semantics.

    ``clip`` bounds every expert's output (labels are standardized, so
    |y| <~ 4).  Assumption (a2) of the paper requires losses in [0, 1],
    which presumes a bounded prediction space; without clipping, the
    non-PSD sigmoid/polynomial "kernels" can emit unbounded predictions
    on tail inputs and (a2) is unsatisfiable.  Recorded in DESIGN.md.
    """
    x = jnp.asarray(x, jnp.float32)
    chunks = []
    # chunk the stream: the Laplacian kernel materializes an
    # (n, anchors, d) pairwise tensor — bounded per chunk
    for lo in range(0, x.shape[0], 2048):
        xc = x[lo:lo + 2048]
        preds = []
        for e in pool.experts:
            if isinstance(e, KernelExpert):
                preds.append(kr_predict(e, xc, use_pallas=use_pallas))
            else:
                preds.append(mlp_apply(e.params, xc))
        chunks.append(jnp.stack(preds, axis=0))
    out = jnp.concatenate(chunks, axis=1)
    if clip is not None:
        out = jnp.clip(out, -clip, clip)
    return out
