"""Pure-jnp oracle for the fused client-eval kernel.

One round of the paper's client-side exchange, as a single pass over the
round's (K, W) prediction window:

* gather the online window ``preds[:, (cursor + 0..W-1) % n_stream]``
  (realized wrap-free on a W-extended stream, see ``extend_stream``),
* the ensemble prediction under the plan's mixture (EFL-FG's eq.-(5)
  softmax or FedBoost's renormalized alpha, zero off the transmit set),
* ``client_window_losses`` — the ensemble/per-model squared-loss
  accumulators with the (a2) normalization ``min(sq / loss_scale, 1)``,
* ``fedboost_window_grad`` — g_k = 2/n_t sum_i (yhat - y_i) f_k(x_i).

The formulas are kept call-for-call identical to the unfused path
(`repro.federated.simulation.client_window_losses` /
``fedboost_window_grad``) so
the fused round body reproduces the unfused trajectories; the Pallas
kernel is tested against this oracle and against independent float64
NumPy implementations in ``tests/test_client_eval.py``.

Semantics at the edges (shared with the unfused path): ``n_t == 0``
yields ``ens_sq_mean = nan`` and ``grad = nan`` (0/0 and inf*0) — an
empty round is meaningless and the engine never produces one
(``n_clients_traceable`` clamps to >= 1); masked accumulators are 0.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
from jax import lax

from repro.core.numerics import ladder_matvec, ladder_sum

__all__ = ["ClientEvalOut", "client_eval_ref", "extend_stream",
           "extended_width", "window_frame", "LANES"]


class ClientEvalOut(NamedTuple):
    ens_sq_mean: jnp.ndarray   # scalar, mean ensemble sq error over n_t
    ens_norm: jnp.ndarray      # scalar, sum of normalized ensemble losses
    model_losses: jnp.ndarray  # (K,) sum of normalized per-model losses
    grad: jnp.ndarray          # (K,) FedBoost mixture gradient


LANES = 128   # TPU vector lane width: compiled lane-dim loads start here


def window_frame(window: int) -> int:
    """Width of the lane-aligned frame the compiled kernel loads: the
    smallest multiple of ``LANES`` that holds a ``window``-wide slice at
    any offset ``0..LANES-1`` from an aligned start."""
    return LANES * -(-(window + LANES - 1) // LANES)


def extended_width(n_stream: int, window: int) -> int:
    """Columns of the extended stream: enough that the compiled kernel's
    frame, which starts at most at ``n_stream - 1`` rounded down to a
    multiple of ``LANES``, never runs past the end."""
    return LANES * -(-n_stream // LANES) + window_frame(window)


def extend_stream(preds: jnp.ndarray, y: jnp.ndarray, window: int):
    """Wrap-free gather trick: append the first ``window`` columns so the
    round's window ``(cursor + 0..window-1) % n_stream`` is the contiguous
    slice ``[cursor, cursor + window)`` of the extended stream (valid for
    every ``cursor < n_stream`` as long as ``window <= n_stream``), then
    zero columns up to ``extended_width`` for the compiled kernel's
    aligned frame (they are never inside a window).

    The extension is loop-invariant — built once per jitted call, *not*
    per round — which is what lets the kernel gather with one dynamic
    slice instead of a K x W modulo gather.
    """
    n_stream = preds.shape[1]
    if window > n_stream:
        raise ValueError(f"window {window} > stream length {n_stream}; "
                         "the wrap-free extension needs window <= n_stream")
    pad = extended_width(n_stream, window) - n_stream - window
    return (jnp.pad(jnp.concatenate([preds, preds[:, :window]], axis=1),
                    ((0, 0), (0, pad))),
            jnp.pad(jnp.concatenate([y, y[:window]]), (0, pad)))


def client_eval_ref(preds_ext: jnp.ndarray, y_ext: jnp.ndarray,
                    cursor: jnp.ndarray, n_t: jnp.ndarray,
                    mix: jnp.ndarray, loss_scale: float, window: int,
                    active=None, shift=None) -> ClientEvalOut:
    """Single-pass jnp reference of the fused round evaluation.

    ``preds_ext``: (K, extended_width) extended predictions;
    ``y_ext``: (extended_width,) extended targets (see
    ``extend_stream``); ``cursor``/``n_t``: int32 scalars; ``mix``: (K,)
    the round's mixture, zero off the transmit set.  Returns
    ``ClientEvalOut``.

    ``active``/``shift`` are the optional per-round schedule operands
    (``repro.scenarios``): a (window,) availability mask ANDed into the
    client mask — per-client means then divide by the surviving count,
    clamped to >= 1 — and a scalar additive label shift.  ``None``
    (the default) traces exactly the stationary program.
    """
    K = preds_ext.shape[0]
    offs = jnp.arange(window)
    cmask = offs < n_t
    if active is not None:
        cmask = cmask & active
    p_cl = lax.dynamic_slice(preds_ext, (jnp.int32(0), cursor), (K, window))
    y_cl = lax.dynamic_slice(y_ext, (cursor,), (window,))
    if shift is not None:
        y_cl = y_cl + shift
    mix = mix.astype(p_cl.dtype)
    sq = (p_cl - y_cl[None, :]) ** 2
    model_losses = ladder_sum(
        jnp.where(cmask[None, :], jnp.minimum(sq / loss_scale, 1.0), 0.0),
        axis=1)
    yhat = ladder_matvec(mix, p_cl)
    ens_sq = jnp.where(cmask, (yhat - y_cl) ** 2, 0.0)
    if active is None:
        nf = n_t.astype(ens_sq.dtype)
    else:
        nf = jnp.maximum(jnp.sum(cmask), 1).astype(ens_sq.dtype)
    ens_sq_mean = ladder_sum(ens_sq) / nf
    ens_norm = ladder_sum(jnp.minimum(ens_sq / loss_scale, 1.0))
    resid = jnp.where(cmask, yhat - y_cl, 0.0)
    grad = (2.0 / nf) * ladder_sum(p_cl * resid[None, :], axis=1)
    return ClientEvalOut(ens_sq_mean, ens_norm, model_losses, grad)
