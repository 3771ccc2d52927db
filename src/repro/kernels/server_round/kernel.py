"""Pallas TPU kernels: fused EFL-FG server round (plan + update).

Two launches per round replace the ~15 small ops of the unfused server
path — two because the client losses arrive *between* them:

* ``server_plan_pallas`` fuses Algorithm 1 (feedback graph), the greedy
  dominating set, the eq.-(4) PMF, the Gumbel-argmax node draw, the
  transmit set, the eq.-(5) mixture and the round cost;
* ``server_update_pallas`` fuses eq.-(7) observation probabilities, the
  eq.-(6)/(8) importance-sampled estimates and both eq.-(9) weight
  updates (the next round's eq.-(2) bound reads the plan's graph).

TPU mapping: everything is K-sized (K=22 at paper scale), so all
operands ride in as whole-array VMEM blocks — vectors as (1, K) rows,
scalars as (1, 1) — and the grid is a singleton, which keeps ``vmap``
(the engine's sweep/batch/serving paths) a single batched-grid dispatch.
The two data-dependent greedy loops become *static* ``fori_loop``s (K-1
append trips, K cover picks): a converged instance's extra trips are
masked no-ops, and its inactivity is monotone (members, cost sums,
the weight bound's excess, covered sets only grow), so the fixed trip
count is bit-preserving — the same argument the graph builder's batched
``custom_vmap`` rule rests on.  Gathers/scatters are rewritten as
one-hot contractions (exact: one term survives), indices come from
``broadcasted_iota`` (1-D ``iota`` does not lower on TPU), and the
argmax-over-ratio replaces the solo path's ``top_k(x, 1)`` — identical
selection semantics (both break ties low, spelled out in ``_argmax_low``
because Mosaic's argmax does not, and takes float32 operands only) on
identical float values.  Two more rules keep the bodies inside what the
Mosaic compiler accepts: masks ride the loops as int32 (no bool loop
carries), and a mask is lifted to a column only as int32.

Numerics: float32 throughout; the surrounding float math *calls the
actual core implementations* (``graph._graph_tables`` /
``_weight_bad``, ``policy.pmf`` / ``ensemble_mix_weights`` /
``observation_probs`` / ``exp_weight_update``) on the same (K,)/(K, K)
shapes, so interpret mode on CPU traces to the same XLA ops as the
unfused server and trajectories stay bit-equal (pinned end-to-end on
the paper config by ``tests/test_server_round.py``).  Compiled on a TPU
the same bodies agree with ``ref`` to float32 rounding, not bit for
bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import policy
from repro.core.graph import _graph_tables, _weight_bad
from repro.core.numerics import ladder_sum, log

from ..platform import prepare_mosaic

__all__ = ["server_plan_pallas", "server_update_pallas"]


def _argmax_low(x, idx, axis=None):
    """``jnp.argmax`` with its tie rule spelled out — the lowest index
    among the maxima.  Mosaic's own argmax breaks ties otherwise, and
    ties are common here: experts of equal cost tie in eq. (3) while the
    weights are still equal, and dominating-set gains are small counts.
    ``idx`` holds each element's index along ``axis``."""
    top = jnp.max(x, axis=axis, keepdims=axis is not None)
    return jnp.min(jnp.where(x == top, idx, x.shape[-1]), axis=axis)


def _server_plan_kernel(log_w_ref, log_u_ref, prev_ref, costs_ref,
                        gumbel_ref, budget_ref, xi_ref,
                        adj_ref, dom_ref, p_ref, drawn_ref, sel_ref,
                        mix_ref, cost_ref, iters_ref, *, K: int):
    log_w = log_w_ref[0, :]
    log_u = log_u_ref[0, :]
    prev = prev_ref[...] != 0
    costs = costs_ref[0, :]
    gumbel = gumbel_ref[0, :]
    budget = budget_ref[0, 0]
    xi = xi_ref[0, 0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)[0]

    # --- Algorithm 1: static-trip form of graph._fg's while body -------
    E, over0, slack, W_ROW = _graph_tables(log_w, costs, budget, prev)

    # loop carries are int32, not bool: Mosaic cannot carry i1 vectors
    def append_trip(_, carry):
        mask, cost_sum, over, iters = carry
        member = mask != 0
        den = cost_sum[:, None] + costs[None, :]
        bad = (member | (den > budget)
               | _weight_bad(E, prev, member, over, slack))
        ratio = jnp.where(bad, -1.0, W_ROW / den)
        d = _argmax_low(ratio, rows[None, :], axis=1)
        # kept 2-D: Mosaic cannot lift a 1-D bool vector to a column
        active = jnp.max(ratio, axis=1, keepdims=True) >= 0.0
        upd = (rows[None, :] == d[:, None]) & active
        mask = mask | upd.astype(jnp.int32)
        # one-hot contraction == costs[d] where active (single survivor)
        cost_sum = cost_sum + jnp.sum(
            jnp.where(upd, costs[None, :], 0.0), axis=1)
        over = over + jnp.sum(jnp.where(upd & ~prev, E, 0.0), axis=1)
        return (mask, cost_sum, over,
                iters + jnp.any(active).astype(jnp.int32))

    adj_i, _, _, iters = jax.lax.fori_loop(
        0, K - 1, append_trip,
        (jnp.eye(K, dtype=jnp.int32), costs, over0, jnp.int32(0)))

    # --- greedy dominating set: static-trip form of domset._ds ---------
    def cover_trip(_, carry):
        dom, unc = carry
        gains = jnp.sum(adj_i * unc[None, :], axis=1)
        gains = jnp.where(dom != 0, -1, gains)
        covering = jnp.any(unc > 0)
        onehot = ((rows == _argmax_low(gains, rows))
                  & covering).astype(jnp.int32)
        dom = dom | onehot
        # integer one-hot row select == adj[argmax] (single survivor)
        row = jnp.sum(onehot[:, None] * adj_i, axis=0)
        return dom, unc * (1 - row)

    dom_i, _ = jax.lax.fori_loop(
        0, K, cover_trip,
        (jnp.zeros((K,), jnp.int32), jnp.ones((K,), jnp.int32)))
    dom = dom_i != 0

    # --- PMF, draw, transmit set, mixture, cost ------------------------
    p = policy.pmf(log_u, dom, xi)
    drawn = _argmax_low(gumbel + log(jnp.maximum(p, 1e-38)), rows)
    # one-hot row select == adj[drawn] (single surviving row)
    sel = jnp.sum((rows == drawn).astype(jnp.int32)[:, None] * adj_i,
                  axis=0) > 0
    mix = policy.ensemble_mix_weights(log_w, sel)
    round_cost = ladder_sum(jnp.where(sel, costs, 0.0))

    adj_ref[...] = adj_i
    dom_ref[...] = dom_i[None, :]
    p_ref[...] = p.astype(p_ref.dtype)[None, :]
    drawn_ref[...] = drawn.astype(jnp.int32).reshape(1, 1)
    sel_ref[...] = sel.astype(jnp.int32)[None, :]
    mix_ref[...] = mix.astype(mix_ref.dtype)[None, :]
    cost_ref[...] = round_cost.astype(cost_ref.dtype).reshape(1, 1)
    iters_ref[...] = iters.reshape(1, 1)


def _server_update_kernel(adj_ref, p_ref, sel_ref, drawn_ref, ml_ref,
                          ens_ref, log_w_ref, log_u_ref, eta_ref,
                          new_w_ref, new_u_ref, *, K: int):
    adj = adj_ref[...] != 0
    p = p_ref[0, :]
    sel = sel_ref[0, :] != 0
    drawn = drawn_ref[0, 0]
    model_losses = ml_ref[0, :]
    ens_loss = ens_ref[0, 0]
    log_w = log_w_ref[0, :]
    log_u = log_u_ref[0, :]
    eta = eta_ref[0, 0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)[0]

    q = policy.observation_probs(adj, p)
    # policy.is_loss_estimates with its arange(K) (1-D iota, no TPU
    # lowering) replaced by the broadcasted-iota rows — same integers
    ell = jnp.where(sel, model_losses / jnp.maximum(q, 1e-12), 0.0)
    ell_hat = jnp.where(rows == drawn,
                        ens_loss / jnp.maximum(p, 1e-12), 0.0)
    new_w = policy.exp_weight_update(log_w, eta, ell)
    new_u = policy.exp_weight_update(log_u, eta, ell_hat)

    new_w_ref[...] = new_w.astype(new_w_ref.dtype)[None, :]
    new_u_ref[...] = new_u.astype(new_u_ref.dtype)[None, :]


_FULL = lambda *_: (0, 0)


def _vec(K):
    return pl.BlockSpec((1, K), _FULL)


def _scalar():
    return pl.BlockSpec((1, 1), _FULL)


@functools.partial(jax.jit, static_argnames=("interpret",))
def server_plan_pallas(log_w, log_u, prev_adj, costs, budget,
                       gumbel, xi, *, interpret: bool = True):
    """Fused planning launch.

    ``log_w``/``log_u``/``costs``/``gumbel``: (K,) f32; ``prev_adj``:
    (K, K) bool/int mask, the previous round's graph;
    ``budget``/``xi``: scalars.  Returns ``(adj (K, K) int32,
    dom (K,) int32, p (K,), drawn int32, sel (K,) int32, mix (K,),
    round_cost, graph_iters int32)`` — the int32 masks are cast to bool
    by the ``ops`` wrapper.
    """
    if not interpret:
        prepare_mosaic()
    K = log_w.shape[0]
    kern = functools.partial(_server_plan_kernel, K=K)
    out_shape = [
        jax.ShapeDtypeStruct((K, K), jnp.int32),    # adj
        jax.ShapeDtypeStruct((1, K), jnp.int32),    # dom
        jax.ShapeDtypeStruct((1, K), jnp.float32),  # p
        jax.ShapeDtypeStruct((1, 1), jnp.int32),    # drawn
        jax.ShapeDtypeStruct((1, K), jnp.int32),    # sel
        jax.ShapeDtypeStruct((1, K), jnp.float32),  # mix
        jax.ShapeDtypeStruct((1, 1), jnp.float32),  # round_cost
        jax.ShapeDtypeStruct((1, 1), jnp.int32),    # graph_iters
    ]
    out_specs = [pl.BlockSpec((K, K), _FULL), _vec(K), _vec(K), _scalar(),
                 _vec(K), _vec(K), _scalar(), _scalar()]
    row = lambda a: jnp.asarray(a, jnp.float32).reshape(1, K)
    outs = pl.pallas_call(
        kern,
        grid=(1,),
        in_specs=[_vec(K), _vec(K), pl.BlockSpec((K, K), _FULL), _vec(K),
                  _vec(K), _scalar(), _scalar()],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(row(log_w), row(log_u), jnp.asarray(prev_adj, jnp.int32), row(costs),
      row(gumbel), jnp.asarray(budget, jnp.float32).reshape(1, 1),
      jnp.asarray(xi, jnp.float32).reshape(1, 1))
    adj, dom, p, drawn, sel, mix, cost, iters = outs
    return (adj, dom[0], p[0], drawn[0, 0], sel[0], mix[0], cost[0, 0],
            iters[0, 0])


@functools.partial(jax.jit, static_argnames=("interpret",))
def server_update_pallas(adj, p, sel, drawn, model_losses, ens_loss,
                         log_w, log_u, eta, *, interpret: bool = True):
    """Fused update launch.

    ``adj``: (K, K) bool/int mask; ``p``/``sel``/``model_losses``/
    ``log_w``/``log_u``: (K,); ``drawn``: int scalar; ``ens_loss``/
    ``eta``: f32 scalars.  Returns ``(log_w, log_u)``, each (K,) f32.
    """
    if not interpret:
        prepare_mosaic()
    K = p.shape[0]
    kern = functools.partial(_server_update_kernel, K=K)
    out_shape = [jax.ShapeDtypeStruct((1, K), jnp.float32)] * 2
    row = lambda a: jnp.asarray(a, jnp.float32).reshape(1, K)
    outs = pl.pallas_call(
        kern,
        grid=(1,),
        in_specs=[pl.BlockSpec((K, K), _FULL), _vec(K), _vec(K), _scalar(),
                  _vec(K), _scalar(), _vec(K), _vec(K), _scalar()],
        out_specs=[_vec(K)] * 2,
        out_shape=out_shape,
        interpret=interpret,
    )(jnp.asarray(adj, jnp.int32), row(p),
      jnp.asarray(sel, jnp.int32).reshape(1, K),
      jnp.asarray(drawn, jnp.int32).reshape(1, 1), row(model_losses),
      jnp.asarray(ens_loss, jnp.float32).reshape(1, 1), row(log_w),
      row(log_u), jnp.asarray(eta, jnp.float32).reshape(1, 1))
    return outs[0][0], outs[1][0]
