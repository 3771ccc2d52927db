"""Feedback-graph generation (Algorithm 1 of the paper).

Each of the K pre-trained models is a vertex.  For every source vertex
``v_k`` we greedily grow an out-neighborhood: starting from the self loop,
repeatedly append the vertex maximizing

    w_i / (sum_{j in N_out} c_j + c_i)                       (eq. 3)

subject to (eq. 2):
  * cumulative cost stays within the round budget ``B_t``,
  * cumulative *weight* of the out-neighborhood does not exceed the
    weight of the row's previous-round out-neighborhood ``P_k`` under
    this round's weights (``W_prev``), with a 1e-6 relative slack,
  * no duplicates.

The weight bound compares the weights of two sets, so it is evaluated on
their difference: a candidate ``i`` for row ``k`` (grown so far to ``A``)
is admitted iff

    sum_{A - P_k} w + w_i  <=  sum_{P_k - A} w + 1e-6 * sum_{P_k} w,

which is ``sum_A w + w_i <= (1 + 1e-6) W_prev`` with the weight of
``A & P_k`` taken off both sides.  A row that stays inside its previous
neighborhood is then admitted exactly on any platform: the left side is
``w_i`` alone and the right side a sum of non-negative terms that holds
it.  Before the first round the previous neighborhood is every node, so
the bound never binds.

The greedy loop is data dependent, so the JAX implementation is a bounded
``lax.while_loop`` (at most K-1 appends) that advances ALL K source
vertices simultaneously with (K, K) array ops — one eligibility
evaluation per append step, no per-row loop machinery.  This runs inside
the simulation engine's ``lax.scan`` hot path, where the flat single-loop
form is severalfold faster than a ``vmap`` of per-row while loops.

Under ``vmap`` (every sweep/batch/serving path) the builder does NOT go
through JAX's generic while-loop batching: a ``custom_vmap`` rule swaps
in a batched-native loop — one flat ``while_loop`` over the whole batch
whose body advances all lanes with (B, K, K) ops, unrolled
``_BATCH_UNROLL``x per trip to amortize loop machinery, with per-lane
done masks so converged lanes execute masked no-ops and their
``n_iters`` stop counting.  The rule is bit-equal to per-lane solo calls
by construction: a lane's inactivity predicate is monotone (members,
cost sums and the weight outside the previous neighborhood only grow,
the weight left inside it only shrinks), so extra trips after a lane
converges change nothing, and every reduction runs over K axes only.

A pure-NumPy reference (`feedback_graph_np`) mirrors the paper's
pseudo-code literally and is used as the oracle in property tests.

Weights are carried in log space throughout the library: after many
exponential-weight updates the raw weights underflow float32, while
log-weights stay exact.  The builder exponentiates them once per round,
shifted per source row (``_graph_tables``).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from .numerics import exp, fma_fence, ladder_sum

__all__ = [
    "feedback_graph",
    "feedback_graph_np",
]

# eq. (2)'s relative slack on the previous neighborhood's weight
_SLACK = 1e-6

# Inner unroll of the batched while body: 2 greedy appends per loop trip
# halves the while-loop machinery overhead (measured ~1.2x on the batched
# graph+domset scan) and stays bit-exact — appends past a lane's
# convergence are masked no-ops, and the trip-parity slack only ever adds
# such no-ops.
_BATCH_UNROLL = 2


def _weight_bad(E, prev, mask, over, slack):
    """Eq. (2)'s weight bound per (row, candidate): True where admitting
    the candidate would lift the row past its previous neighborhood's
    weight.  ``under`` is recomputed from the members, never decremented,
    so it stays monotone and exact for rows inside ``prev``."""
    under = ladder_sum(jnp.where(prev & ~mask, E, 0.0), axis=-1,
                       fence=False)
    return over[..., None] + E > (under + slack)[..., None]


def _graph_tables(log_w, costs, budget, prev):
    """Per-round precomputation shared by the solo and batched loops and
    the fused server kernel.

    The while body runs on the scan engine's hot path, where every (K, K)
    op costs ~1us of dispatch on CPU, so the log-weights are
    exponentiated once per round:
      eq. (2) weight bound:  ``E[k, j] = exp(log_w_j - m_k)``, shifted
        per row by the row's largest previous-neighborhood log-weight
        ``m_k`` (so ``sum_{P_k} E`` lies in [1, K]; a node far above it
        overflows to inf, which only ever refuses it).  A row ``A``
        carries ``over = sum_{A - P_k} E`` (grown by exact one-hot
        adds) and recomputes ``under = sum_{P_k - A} E`` as a ladder
        sum each trip; candidate ``i`` fails iff
        ``over + E[k, i] > under + slack_k`` with
        ``slack_k = 1e-6 * sum_{P_k} E``.  For ``A`` inside ``P_k``,
        ``over`` is exactly 0 and ``under`` a sum of non-negative terms
        that holds ``E[k, i]`` — rounding is monotone, so such a
        candidate is never refused.
      eq. (3) argmax:  log_w_j - log(den) -> W_ROW[i, j] / den, with
        ``W_ROW[i, j] = exp(log_w_j - shift_i)`` shifted per row by the
        row's best *initially eligible* log-weight — so each row's
        leading candidate scores ~1/den whatever the global weight
        spread.  Within a row the shift is a constant positive factor:
        the argmax is unchanged.

    Returns ``(E, over0, slack, W_ROW)`` with shapes (K, K), (K,), (K,),
    (K, K).
    """
    K = log_w.shape[0]
    eye = jnp.eye(K, dtype=bool)
    m = jnp.max(jnp.where(prev, log_w[None, :], -jnp.inf), axis=1)
    m = jnp.where(jnp.isfinite(m), m, 0.0)             # empty-row guard
    E = exp(log_w[None, :] - m[:, None])
    # fenced: the product must round before ``under + slack`` in every
    # program variant (numerics.fma_fence)
    slack = fma_fence(_SLACK * ladder_sum(jnp.where(prev, E, 0.0), axis=1,
                                          fence=False))
    # one term: the self loop's weight when it lies outside P_k
    over0 = jnp.sum(jnp.where(eye & ~prev, E, 0.0), axis=1)
    # Row shift = the row's best log-weight among its round-start
    # eligible set (self/over-budget/over-weight excluded).  Rows with no
    # eligible candidate fall back to shift 0 (log_w <= 0 throughout the
    # library, so exp stays bounded); they append nothing either way.
    den0 = costs[:, None] + costs[None, :]
    bad0 = eye | (den0 > budget) | _weight_bad(E, prev, eye, over0, slack)
    best = jnp.max(jnp.where(bad0, -jnp.inf, log_w[None, :]), axis=1)
    shift = jnp.where(jnp.isfinite(best), best, 0.0)
    W_ROW = exp(log_w[None, :] - shift[:, None])
    return E, over0, slack, W_ROW


@jax.custom_batching.custom_vmap
def _fg(log_w, costs, budget, prev):
    """Solo Algorithm 1: ``(K,) args -> (adjacency (K, K), n_iters)``."""
    K = log_w.shape[0]
    rows = jnp.arange(K)
    E, over0, slack, W_ROW = _graph_tables(log_w, costs, budget, prev)

    def body(carry):
        mask, cost_sum, over, _, iters = carry
        den = cost_sum[:, None] + costs[None, :]
        # ineligibility folded into one sentinel chain: eligible ratios are
        # >= 0 (W_ROW, den > 0), so -1 marks members/over-budget/over-weight
        bad = (mask | (den > budget)
               | _weight_bad(E, prev, mask, over, slack))
        ratio = jnp.where(bad, -1.0, W_ROW / den)
        best, idx = jax.lax.top_k(ratio, 1)                # one fused kernel
        d = idx[:, 0]                                      # (K,) appends
        active = best[:, 0] >= 0.0                         # any eligible?
        # one-hot append instead of 2D scatter/gather (XLA CPU scatter is
        # an order of magnitude slower than the fusable elementwise form)
        upd = (rows[None, :] == d[:, None]) & active[:, None]
        mask = mask | upd
        cost_sum = cost_sum + jnp.where(active, costs[d], 0.0)
        over = over + jnp.sum(jnp.where(upd & ~prev, E, 0.0), axis=1)
        any_active = jnp.any(active)
        return (mask, cost_sum, over, any_active,
                iters + any_active.astype(jnp.int32))

    carry0 = (jnp.eye(K, dtype=bool),                      # self loops
              costs, over0, jnp.bool_(True), jnp.int32(0))
    mask, _, _, _, iters = jax.lax.while_loop(lambda c: c[3], body, carry0)
    return mask, iters


@_fg.def_vmap
def _fg_batched(axis_size, in_batched, log_w, costs, budget, prev):
    """Batched-native Algorithm 1: one flat while_loop over the batch.

    Replaces JAX's generic while-loop batching (which would re-trace the
    solo body under vmap) with a hand-batched loop: per-lane done masks,
    ``_BATCH_UNROLL`` appends per trip, per-lane ``n_iters`` counters
    that freeze on convergence.  Bit-equal to per-lane solo calls —
    pinned by ``tests/test_feedback_graph_batched.py``.
    """
    B = axis_size

    def bcast(x, batched):
        x = jnp.asarray(x)
        return x if batched else jnp.broadcast_to(x, (B,) + x.shape)

    log_w, costs, budget, prev = (
        bcast(a, b) for a, b in zip(
            (log_w, costs, budget, prev), in_batched))
    K = log_w.shape[-1]
    rows = jnp.arange(K)
    E, over0, slack, W_ROW = jax.vmap(_graph_tables)(log_w, costs, budget,
                                                     prev)

    def one(c):
        mask, cs, over, it = c
        den = cs[..., None] + costs[:, None, :]
        bad = (mask | (den > budget[:, None, None])
               | _weight_bad(E, prev, mask, over, slack))
        ratio = jnp.where(bad, -1.0, W_ROW / den)
        best, idx = jax.lax.top_k(ratio, 1)
        d = idx[..., 0]                                    # (B, K)
        active = best[..., 0] >= 0.0
        upd = (rows[None, None, :] == d[..., None]) & active[..., None]
        mask = mask | upd
        cs = cs + jnp.where(active,
                            jnp.take_along_axis(costs, d, axis=-1), 0.0)
        over = over + jnp.sum(jnp.where(upd & ~prev, E, 0.0), axis=-1)
        it = it + jnp.any(active, axis=-1).astype(jnp.int32)
        return (mask, cs, over, it), active

    def body(cc):
        c, _ = cc
        for _ in range(_BATCH_UNROLL):
            c, active = one(c)
        return c, jnp.any(active)

    carry0 = (jnp.tile(jnp.eye(K, dtype=bool)[None], (B, 1, 1)),
              costs, over0, jnp.zeros((B,), jnp.int32))
    (mask, _, _, iters), _ = jax.lax.while_loop(
        lambda cc: cc[1], body, (carry0, jnp.bool_(True)))
    return (mask, iters), (True, True)


@functools.partial(jax.jit, static_argnames=("with_iters",))
def feedback_graph(log_w: jnp.ndarray, costs: jnp.ndarray, budget: jnp.ndarray,
                   prev: jnp.ndarray, *, with_iters: bool = False):
    """Algorithm 1.  Returns the boolean adjacency ``A`` with
    ``A[k, i] = True`` iff ``v_i`` is an out-neighbor of ``v_k`` — or
    ``(A, n_iters)`` with ``with_iters``, where ``n_iters`` is the number
    of *productive* append steps this instance needed to converge.

    All K out-neighborhoods grow in lockstep: each ``while_loop`` step
    appends every still-eligible row's eq.-(3) argmax; rows whose eligible
    set is empty stop changing, and the loop exits once a full step
    appends nothing (at most K-1 productive steps + 1 no-op step).

    Under ``vmap`` (every sweep/batch/serving path) a ``custom_vmap``
    rule swaps in the batched-native loop (module docstring): converged
    lanes ride as masked no-ops instead of re-running the solo body, and
    results stay bit-equal to per-lane solo calls.  ``n_iters`` is each
    instance's OWN productive count either way — the engine records it
    per round and ``SweepResult.lockstep_waste`` aggregates the residual
    idle iterations of co-scheduled lanes
    (docs/architecture.md#known-limitations).

    Precision note: the exp-space form trades the log-space form's
    unbounded dynamic range for speed.  The eq.-(3) scores are max-shifted
    *per source row* by the row's best initially-eligible log-weight, so
    a high-weight but ineligible leader (over budget, already a member)
    cannot underflow the scores of the candidates that actually compete.
    The residual degeneracy is narrow: candidates trailing their own
    row's best eligible candidate by more than ~88 nats underflow to a
    0 score and that argmax falls back to lowest-index (they stay
    eligible and still join the neighborhood).  At the paper's horizons
    the spread stays far below that (~45 nats at T=2000) and such models
    carry negligible eq.-(5) mixture weight anyway; for extreme horizons,
    re-derive eta or shard the run before the spread approaches float32
    exp range.  The eq.-(2) weights are shifted per row by the previous
    neighborhood's largest log-weight: a weight ~88 nats below it
    underflows to 0, far inside the bound's 1e-6 slack.

    Args:
      log_w: (K,) log confidence weights ``log w_{k,t}``.
      costs: (K,) transmission costs ``c_k`` (positive).
      budget: scalar round budget ``B_t``.
      prev: (K, K) bool, the previous round's adjacency ``N_out_{k,t-1}``
        (self loops included); eq. (2) bounds each row by its weight
        under ``log_w``.  Pass all-True on the first round, where no
        previous neighborhood exists: every row lies inside it, so the
        bound never binds.
    """
    K = log_w.shape[-1]
    if jnp.shape(prev) != (K, K):
        raise ValueError(f"feedback_graph: prev must be the previous (K, K) "
                         f"adjacency, K={K}; got shape {jnp.shape(prev)}")
    with jax.named_scope("round.graph"):
        mask, iters = _fg(log_w, costs, jnp.asarray(budget),
                          jnp.asarray(prev, bool))
    return (mask, iters) if with_iters else mask


# ---------------------------------------------------------------------------
# NumPy reference, literal transcription of Algorithm 1 (test oracle).
# ---------------------------------------------------------------------------

def feedback_graph_np(w: np.ndarray, costs: np.ndarray, budget: float,
                      prev=None) -> np.ndarray:
    """Literal Algorithm 1 on raw (non-log) weights. Returns bool (K, K).

    ``prev`` is the previous round's adjacency; ``None`` (the first
    round) leaves the weight bound out."""
    K = len(w)
    adj = np.zeros((K, K), dtype=bool)
    for k in range(K):
        w_prev = (np.inf if prev is None
                  else sum(w[j] for j in range(K) if prev[k][j]))
        out = {k}
        while True:
            cost_sum = sum(costs[j] for j in out)
            wsum = sum(w[j] for j in out)
            # eq. (2): the eligible set M_{k,t}
            elig = [i for i in range(K)
                    if i not in out
                    and cost_sum + costs[i] <= budget
                    and wsum + w[i] <= w_prev * (1 + 1e-6)]
            if not elig:
                break
            # eq. (3)
            d = max(elig, key=lambda i: w[i] / (cost_sum + costs[i]))
            out.add(d)
        adj[k, list(out)] = True
    return adj
