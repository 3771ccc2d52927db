"""Public fused client-eval op: backend dispatch for the Pallas kernel.

``client_eval`` is the round-body entry point: interpret mode on CPU
(the kernel body traces to the same XLA ops as the unfused path, so the
fused round body keeps its trajectories), compiled Pallas on TPU, and an
error on any other backend (``repro.kernels.platform``).  The
engine (`repro.federated.simulation.make_round_body`) calls it once per
round behind ``SimConfig.use_fused``; ``extend_stream`` (re-exported
from ``ref``) prepares the wrap-free stream operands once per jitted
call.
"""

from __future__ import annotations

import jax.numpy as jnp

from ..platform import interpret_mode
from .kernel import client_eval_pallas
from .ref import ClientEvalOut, extend_stream

__all__ = ["client_eval", "extend_stream", "ClientEvalOut"]


def client_eval(preds_ext: jnp.ndarray, y_ext: jnp.ndarray,
                cursor: jnp.ndarray, n_t: jnp.ndarray, mix: jnp.ndarray, *,
                loss_scale: float, window: int, with_grad: bool = True,
                interpret: bool | None = None,
                active=None, shift=None) -> ClientEvalOut:
    """One fused round of client-side evaluation (see ``ref.client_eval_ref``
    for exact semantics).  ``grad`` is zeros-shaped ``None``-free only when
    ``with_grad`` is set; the EFL-FG path skips it.

    ``active``/``shift`` are the optional per-round schedule operands
    (participation mask + label drift, ``repro.scenarios``) — absent on
    the stationary path, which keeps its pre-scenario launch signature.
    """
    if interpret is None:
        interpret = interpret_mode()
    ens_sq_mean, ens_norm, model_losses, grad = client_eval_pallas(
        preds_ext, y_ext, cursor, n_t, mix, loss_scale=loss_scale,
        window=window, with_grad=with_grad, interpret=interpret,
        active=active, shift=shift)
    return ClientEvalOut(ens_sq_mean, ens_norm, model_losses, grad)
