"""Pallas TPU kernel: fused per-round client evaluation.

One launch per simulation round fuses the whole client-side exchange —
window gather, the ensemble prediction under the plan's mixture, the
ensemble/per-model squared-loss accumulators, and the FedBoost mixture
gradient — into a single pass over the round's (K, W) prediction tile,
replacing the ~6 small ops the unfused round body dispatches per round.

TPU mapping: the extended stream (K, extended_width) and targets ride
in as whole-array VMEM operands — at the paper's largest stream (K=22,
n_stream=17,761, f32) that is ~1.7 MiB, under the 16 MiB scoped VMEM
budget — and the round window is a *dynamic-start* contiguous load,
wrap-free thanks to the W-column extension (see ``ref.extend_stream``).
Interpret mode loads exactly ``preds[:, ds(cursor, W)]``; Mosaic loads
lane windows only from multiples of 128, so the compiled kernel loads
the aligned ``window_frame(W)``-wide frame holding the window and masks
the columns outside it.  The cursor / client-count scalars arrive as
(1, 1) operands.  All downstream compute is one (1, K) x (K, F) MXU
matvec plus VPU elementwise/reduction work, so a single grid step
suffices; streams too large for VMEM residency would move ``preds`` to
HBM with an async-DMA'd window (future work).

The grid is a singleton, which also keeps ``jax.vmap`` batching (the
engine's sweep path) a *single* batched-grid dispatch per round rather
than one launch per sweep lane.

Numerics: float32 throughout, formula-for-formula identical to the
unfused path (`simulation.client_window_losses`,
``simulation.fedboost_window_grad``);
interpret mode on CPU executes the same XLA ops, so fused-vs-unfused
round trajectories agree to float32 rounding (empirically bit-equal
selection masks on the paper config — pinned by the benchmark's
``fused_trajectories_identical`` field and ``tests/test_client_eval.py``).
Compiled on a TPU, plain reductions replace the ladders and the kernel
agrees with ``ref`` to float32 rounding, not bit for bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.numerics import ladder_matvec, ladder_sum

from ..platform import prepare_mosaic
from .ref import LANES, window_frame

__all__ = ["client_eval_pallas"]


def _client_eval_kernel(preds_ref, y_ref, cursor_ref, nt_ref, mix_ref,
                        active_ref, shift_ref, scal_ref, ml_ref, grad_ref,
                        *, loss_scale: float, window: int,
                        with_grad: bool, interpret: bool):
    # preds_ref: (K, L); y_ref: (1, L); cursor/nt: (1, 1) int32;
    # mix_ref: (1, K); outputs: scal (1, 2), ml/grad (K, 1).  active_ref (1, W) int32 / shift_ref (1, 1) f32 are the
    # optional per-round schedule operands (repro.scenarios) — ``None`` on
    # the stationary path, which then traces exactly the pre-scenario ops.
    cursor = cursor_ref[0, 0]
    n_t = nt_ref[0, 0]
    if interpret:
        # the exact window, gathered as the unfused path gathers it
        pw = preds_ref[:, pl.ds(cursor, window)]        # (K, W)
        yw = y_ref[:, pl.ds(cursor, window)]            # (1, W)
        offs = jax.lax.broadcasted_iota(jnp.int32, (1, window), 1)
        cmask = offs < n_t
    else:
        # Mosaic loads a lane window only from a multiple of 128: load
        # the aligned frame that holds the window and mask the rest (the
        # wrapper places ``active`` at the same offset in the frame)
        frame = window_frame(window)
        off = cursor % LANES
        start = pl.multiple_of(cursor - off, LANES)
        pw = preds_ref[:, pl.ds(start, frame)]          # (K, F)
        yw = y_ref[:, pl.ds(start, frame)]              # (1, F)
        offs = jax.lax.broadcasted_iota(jnp.int32, (1, frame), 1) - off
        cmask = (offs >= 0) & (offs < n_t)
    if shift_ref is not None:
        yw = yw + shift_ref[0, 0]                       # concept drift
    if active_ref is not None:
        cmask = cmask & (active_ref[...] != 0)          # participation

    def lane_sum(x):                                    # (R, n) -> (R, 1)
        if interpret:
            # ladder reductions: same fixed add tree as the unfused
            # ``client_window_losses`` (see repro.core.numerics) so the
            # two execution strategies stay bit-equal in every fusion
            # context
            return ladder_sum(x, axis=1)[:, None]
        return jnp.sum(x, axis=1, keepdims=True)

    mix = mix_ref[...]                                  # (1, K)

    sq = (pw - yw) ** 2                                 # (K, W) broadcast
    ml_ref[...] = lane_sum(
        jnp.where(cmask, jnp.minimum(sq / loss_scale, 1.0), 0.0)
    ).astype(ml_ref.dtype)

    if interpret:
        yhat = ladder_matvec(mix, pw)                   # (1, W)
    else:
        # the MXU at full float32 precision (the default would round the
        # operands to bfloat16)
        yhat = jnp.dot(mix, pw, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    ens_sq = jnp.where(cmask, (yhat - yw) ** 2, 0.0)
    if active_ref is None:
        nf = n_t.astype(ens_sq.dtype)
    else:
        # means divide by the SURVIVING client count (clamped >= 1 —
        # slot 0 is always compiled active, see Participation.mask)
        nf = jnp.maximum(jnp.sum(cmask.astype(jnp.int32), axis=1,
                                 keepdims=True), 1).astype(ens_sq.dtype)
    scal_ref[:, 0:1] = (lane_sum(ens_sq) / nf).astype(scal_ref.dtype)
    scal_ref[:, 1:2] = lane_sum(
        jnp.minimum(ens_sq / loss_scale, 1.0)).astype(scal_ref.dtype)

    if with_grad:
        # the FedBoost alpha trajectory feeds back on itself, so on the
        # CPU even a 1-ulp difference from the unfused
        # ``fedboost_window_grad`` would amplify over rounds: the
        # interpreter takes its ladder contraction
        resid = jnp.where(cmask, yhat - yw, 0.0)        # (1, W)
        grad = (2.0 / nf) * lane_sum(pw * resid)        # (K, 1)
        grad_ref[...] = grad.astype(grad_ref.dtype)


@functools.partial(jax.jit, static_argnames=("loss_scale", "window",
                                             "with_grad", "interpret"))
def client_eval_pallas(preds_ext: jnp.ndarray, y_ext: jnp.ndarray,
                       cursor: jnp.ndarray, n_t: jnp.ndarray,
                       mix: jnp.ndarray, *, loss_scale: float, window: int,
                       with_grad: bool = True, interpret: bool = True,
                       active=None, shift=None):
    """Fused client-eval launch.

    ``preds_ext``: (K, extended_width) f32; ``y_ext``: (extended_width,)
    f32 (see ``ref.extend_stream``); ``cursor``/``n_t``: int32 scalars;
    ``mix``: (K,), the round's mixture, zero off the transmit set.
    Returns ``(ens_sq_mean, ens_norm, model_losses, grad)`` with
    ``grad = None`` when ``with_grad`` is off (the EFL-FG path needs no
    mixture gradient).

    ``active`` ((window,) bool) and ``shift`` (scalar f32) are the
    optional schedule operands of the scenario path
    (``repro.scenarios``); both-or-neither.  When absent the launch has
    exactly the pre-scenario operand list, so stationary programs are
    untouched.
    """
    if (active is None) != (shift is None):
        raise ValueError("schedule operands come together: pass both "
                         "active and shift, or neither")
    if not interpret:
        prepare_mosaic()
    scheduled = active is not None
    K, SW = preds_ext.shape
    kern = functools.partial(_client_eval_kernel, loss_scale=loss_scale,
                             window=window, with_grad=with_grad,
                             interpret=interpret)
    kern = _adapt_refs(kern, with_grad=with_grad, scheduled=scheduled)
    full = lambda *_: (0, 0)
    out_shape = [
        jax.ShapeDtypeStruct((1, 2), jnp.float32),   # [ens_sq_mean, ens_norm]
        jax.ShapeDtypeStruct((K, 1), jnp.float32),   # model_losses
        jax.ShapeDtypeStruct((K, 1), jnp.float32),   # grad
    ]
    out_specs = [pl.BlockSpec((1, 2), full), pl.BlockSpec((K, 1), full),
                 pl.BlockSpec((K, 1), full)]
    if not with_grad:
        out_shape, out_specs = out_shape[:2], out_specs[:2]
    in_specs = [
        pl.BlockSpec((K, SW), full),
        pl.BlockSpec((1, SW), full),
        pl.BlockSpec((1, 1), full),
        pl.BlockSpec((1, 1), full),
        pl.BlockSpec((1, K), full),
    ]
    operands = [preds_ext.astype(jnp.float32),
                y_ext.astype(jnp.float32).reshape(1, SW),
                jnp.asarray(cursor, jnp.int32).reshape(1, 1),
                jnp.asarray(n_t, jnp.int32).reshape(1, 1),
                jnp.asarray(mix, jnp.float32).reshape(1, K)]
    if scheduled:
        active = jnp.asarray(active, jnp.int32)
        if not interpret:
            # place the mask where the window sits in the kernel's frame
            active = jax.lax.dynamic_update_slice(
                jnp.zeros(window_frame(window), jnp.int32), active,
                (jnp.asarray(cursor, jnp.int32) % LANES,))
        in_specs += [pl.BlockSpec((1, active.shape[0]), full),
                     pl.BlockSpec((1, 1), full)]
        operands += [active.reshape(1, -1),
                     jnp.asarray(shift, jnp.float32).reshape(1, 1)]
    outs = pl.pallas_call(
        kern,
        grid=(1,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)
    scal, ml = outs[0], outs[1][:, 0]
    grad = outs[2][:, 0] if with_grad else None
    return scal[0, 0], scal[0, 1], ml, grad


def _adapt_refs(kern, with_grad: bool, scheduled: bool):
    """Adapt the full 10-ref kernel body to the launch's actual ref list
    (the schedule operands and the grad output are both optional)."""
    def wrapped(*refs):
        refs = list(refs)
        ins, i = refs[:5], 5
        active_ref = shift_ref = None
        if scheduled:
            active_ref, shift_ref = refs[5], refs[6]
            i = 7
        scal_ref, ml_ref = refs[i:i + 2]
        grad_ref = refs[i + 2] if with_grad else None
        kern(*ins, active_ref, shift_ref, scal_ref, ml_ref, grad_ref)
        return
    return wrapped
