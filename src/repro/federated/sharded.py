"""Distributed client evaluation: the paper's comm pattern on a JAX mesh.

The paper's round has three wire transfers:
  1. server -> clients: the selected models (budgeted broadcast),
  2. clients: local loss computation,
  3. clients -> server: per-model losses (uplink, reduced at the server).

On a TPU mesh we map clients onto the ``data`` axis: every device simulates
an equal shard of the round's client cohort, evaluates the transmitted
experts on its local samples, and the server reduction (3) becomes a
``psum`` over ``data``.  The broadcast (1) is the implicit replication of
the selected experts' parameters (their sharding spec has no ``data``
axis).  This is the TPU-native adaptation recorded in DESIGN.md §4 — there
is no NCCL-style point-to-point emulation, just collectives.

``sharded_round_losses`` is the shard_map kernel; ``make_client_eval``
binds it to a mesh.  It works for any per-device expert-prediction
function, so the LLM-pool example reuses it.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.numerics import ladder_matvec, ladder_sum

__all__ = ["sharded_round_losses", "sharded_window_eval", "make_client_eval"]


def sharded_round_losses(preds: jnp.ndarray, y: jnp.ndarray,
                         mix: jnp.ndarray, loss_scale: float,
                         axis: str = "data"):
    """Per-device body: local client shard -> (model_losses, ens_loss).

    Must be called inside a ``shard_map`` (or ``pmap``) that binds ``axis``
    (``make_client_eval`` wraps it); shapes below are the *per-device*
    shards.

    preds: (K, n_local) float32 expert predictions on this device's clients
      — the client axis is sharded over ``axis``, so the global cohort is
      (K, n_local * axis_size).
    y: (n_local,) float32 labels, sharded like ``preds``.
    mix: (K,) float32 eq.-(5) mixture weights, replicated over ``axis``
      (they rode down with the server broadcast).

    Returns a replicated tuple (every element is ``psum``-reduced over
    ``axis``, i.e. identical on all devices — what the server sees after
    the uplink reduction):
      model_losses: (K,) summed normalized per-model losses,
      ens_loss:     scalar summed normalized ensemble loss,
      ens_sq_sum:   scalar summed raw ensemble squared error.

    Determinism: per-device partial sums are reduced by ``psum``, whose
    cross-device combine order is fixed by the mesh, so repeated runs on
    the same mesh are bit-identical; against a *single-device* evaluation
    of the same cohort the float32 sums may differ in the last ulp
    (different reduction grouping).
    """
    sq = (preds - y[None, :]) ** 2
    model_losses = jnp.minimum(sq / loss_scale, 1.0).sum(axis=1)
    yhat = mix @ preds
    ens_sq = (yhat - y) ** 2
    ens_loss = jnp.minimum(ens_sq / loss_scale, 1.0).sum()
    model_losses = jax.lax.psum(model_losses, axis)
    ens_loss = jax.lax.psum(ens_loss, axis)
    ens_sq_sum = jax.lax.psum(ens_sq.sum(), axis)
    return model_losses, ens_loss, ens_sq_sum


def sharded_window_eval(preds: jnp.ndarray, y: jnp.ndarray,
                        cursor: jnp.ndarray, n_t: jnp.ndarray,
                        mix: jnp.ndarray, loss_scale: float, window: int,
                        *, axis: str, axis_size: int,
                        with_grad: bool = False, active=None, shift=None):
    """Data-parallel ``simulation.client_window_losses`` (+ FedBoost grad).

    The engine's round body evaluates a fixed ``window``-wide slice of the
    online stream starting at ``cursor``, with the first ``n_t`` positions
    active.  Here that window is split into ``axis_size`` contiguous
    chunks: the device at ``lax.axis_index(axis)`` gathers and evaluates
    the *elementwise* client losses for window positions
    ``[d*w_local, (d+1)*w_local)`` (``w_local = window // axis_size`` —
    the caller guarantees divisibility); the chunks are then
    ``all_gather``-ed back to the full (K, window) layout and reduced
    full-width on every device.  This is the 2-D ``(sweep, data)`` mesh
    composition used by ``repro.federated.engine.run_sweep_sharded``.

    Why all_gather + full-width reduce, not a psum of per-chunk partial
    sums (``sharded_round_losses``' reduction)?  Chunked partial sums
    change the float32 reduction grouping by a last-ulp, and EFL-FG's
    graph draw chaotically amplifies that into *different selection
    trajectories* within a few hundred rounds.  Gathering the uplinked
    per-position losses and reducing them in the exact layout the
    single-device engine reduces keeps the sharded sweep bit-equal to the
    vmap path (pinned by tests/test_sweep_sharding.py) — and mirrors the
    paper's wire protocol anyway: clients uplink losses, the *server*
    reduces.  ``sharded_round_losses`` keeps its cheaper psum for the
    standalone cohort evaluation, where no scan feeds back into a draw.

    Must be called inside a ``shard_map`` binding ``axis``.  ``preds``
    (K, n_stream) and ``y`` (n_stream,) are *replicated* over ``axis``
    (the window chunking, not input sharding, distributes the work — the
    sequential stream gather wraps modulo ``n_stream`` and may cross any
    shard boundary).

    ``active``/``shift`` are the optional per-round schedule operands
    (``repro.scenarios``), both replicated over ``axis``: the (window,)
    availability mask is chunk-sliced and ANDed into the client mask
    (the surviving count is all-gathered so every device divides by the
    same global denominator), the scalar label shift is added to the
    observed targets.  ``None`` traces the stationary program.

    Returns ``(ens_sq_mean, ens_loss_norm, model_losses_norm, grad)`` with
    the same semantics/shapes as ``client_window_losses`` (+ the (K,)
    mixture gradient, or ``None`` without ``with_grad``), replicated over
    ``axis``.
    """
    n_stream = preds.shape[1]
    w_local = window // axis_size
    dev = jax.lax.axis_index(axis)
    offs = dev * w_local + jnp.arange(w_local)
    idx = (cursor + offs) % n_stream
    cmask = offs < n_t
    if active is not None:
        cmask = cmask & jax.lax.dynamic_slice(active, (dev * w_local,),
                                              (w_local,))
    p_cl = preds[:, idx]                           # (K, w_local) chunk
    y_cl = y[idx]
    if shift is not None:
        y_cl = y_cl + shift
    sq = (p_cl - y_cl[None, :]) ** 2
    ml_chunk = jnp.where(cmask[None, :],
                         jnp.minimum(sq / loss_scale, 1.0), 0.0)
    # ladder reductions (core.numerics) exactly mirror
    # simulation.client_window_losses: the K-axis ladder is per-position,
    # so computing yhat on the chunk equals computing it full-width
    yhat = ladder_matvec(mix, p_cl)
    ens_sq_chunk = jnp.where(cmask, (yhat - y_cl) ** 2, 0.0)
    # uplink: device-order tiled gather reassembles the full window layout
    ml = jax.lax.all_gather(ml_chunk, axis, axis=1, tiled=True)  # (K, W)
    ens_sq = jax.lax.all_gather(ens_sq_chunk, axis, axis=0, tiled=True)
    model_losses = ladder_sum(ml, axis=1)
    if active is None:
        n_eff = n_t
    else:
        cm = jax.lax.all_gather(cmask, axis, axis=0, tiled=True)  # (W,)
        n_eff = jnp.maximum(jnp.sum(cm), 1)
    ens_sq_mean = ladder_sum(ens_sq) / n_eff.astype(ens_sq.dtype)
    ens_loss = ladder_sum(jnp.minimum(ens_sq / loss_scale, 1.0))
    grad = None
    if with_grad:
        resid_chunk = jnp.where(cmask, yhat - y_cl, 0.0)
        resid = jax.lax.all_gather(resid_chunk, axis, axis=0, tiled=True)
        # preds is replicated, so the full-window prediction gather is a
        # local lookup — no collective needed, and the values (hence the
        # ladder products) are bit-identical to gathering the chunks.
        idx_full = (cursor + jnp.arange(window)) % n_stream
        grad = (2.0 / n_eff.astype(resid.dtype)) \
            * ladder_sum(preds[:, idx_full] * resid[None, :], axis=1)
    return ens_sq_mean, ens_loss, model_losses, grad


def make_client_eval(mesh: Mesh, loss_scale: float = 4.0, axis: str = "data"):
    """shard_map-wrapped ``sharded_round_losses`` over the mesh's ``axis``.

    Returns a jitted ``fn(preds, y, mix) -> (model_losses, ens_loss,
    ens_sq_sum)`` taking *global* arrays: the (K, n) prediction matrix and
    (n,) labels are sharded over clients (``n`` must divide the axis
    size), the (K,) mixture weights are replicated (they rode down with
    the broadcast).  Outputs are replicated — exactly what the server
    sees after the uplink reduction.  Works for any per-device
    expert-prediction source, so the LLM-pool example reuses it.
    """
    fn = partial(sharded_round_losses, loss_scale=loss_scale, axis=axis)
    return jax.jit(shard_map(
        fn, mesh=mesh,
        in_specs=(P(None, axis), P(axis), P(None)),
        out_specs=(P(None), P(), P()),
    ))
