"""Plain NumPy reference of the federated round, for deciding ``correct``.

A straightforward transcription of the paper's algorithms (arXiv:2202.13447,
Algorithms 1 and 2, eqs. 2-10) and of the streaming FedBoost baseline the
paper compares against, vectorised over lanes (one lane = one simulated
federation: one seed and one budget).  It imports nothing of the program
under test; it shares with it only the data (the experts' predictions on the
stream, the labels, the transmission costs) and the seed-to-randomness map
(``draw_uniforms``), which is part of what a seed means.

Everything is computed in the dtype given (float64 for the reference; a lower
precision for the control), with the expert weights in raw (not log) space as
the paper writes them.
"""

from __future__ import annotations

import numpy as np

__all__ = ["draw_uniforms", "costs_from_sizes", "run_lanes", "project_simplex"]


def draw_uniforms(seeds, T: int, K: int, algo: str) -> np.ndarray:
    """The uniforms each lane's round draws from, ``(L, T, K)`` float32.

    A lane seeded with ``s`` starts from ``PRNGKey(s)`` and splits off one
    draw key per round (``key, k = split(key)``).  EFL-FG draws its node by
    the Gumbel-max trick from uniforms on ``[tiny, 1)``; FedBoost includes
    expert ``k`` when its uniform on ``[0, 1)`` falls below ``pi_k``.
    """
    import jax
    import jax.numpy as jnp

    minval = jnp.finfo(jnp.float32).tiny if algo == "eflfg" else 0.0

    def lane(seed):
        def step(key, _):
            key, k = jax.random.split(key)
            return key, jax.random.uniform(k, (K,), jnp.float32,
                                           minval=minval, maxval=1.0)
        return jax.lax.scan(step, jax.random.PRNGKey(seed), None,
                            length=T)[1]

    seeds = jnp.asarray(np.asarray(seeds, np.uint32))
    return np.asarray(jax.jit(jax.vmap(lane))(seeds))


def costs_from_sizes(n_rows: int, d: int, hidden: int,
                     n_kernel: int = 20) -> np.ndarray:
    """Transmission costs of the paper's pool: parameters over the largest.

    A kernel expert sends its ``n_rows`` anchors of width ``d`` and as many
    coefficients; an MLP with one or two hidden layers of ``hidden`` units
    sends its weights and biases."""
    kernel = n_rows * d + n_rows
    mlp1 = d * hidden + hidden + hidden + 1
    mlp2 = d * hidden + hidden + hidden * hidden + hidden + hidden + 1
    n = np.array([kernel] * n_kernel + [mlp1, mlp2], np.float64)
    return n / n.max()


def project_simplex(v: np.ndarray) -> tuple:
    """Euclidean projection of each row onto the probability simplex:
    ``(max(z, 0), z)`` with ``z = v + lambda`` before the clamp."""
    dt = v.dtype.type
    K = v.shape[-1]
    u = -np.sort(-v, axis=-1)
    css = np.cumsum(u, axis=-1, dtype=v.dtype)
    ks = np.arange(1, K + 1).astype(v.dtype)
    cond = u + (dt(1) - css) / ks > 0
    rho = np.max(np.where(cond, np.arange(K), -1), axis=-1)
    lam = (dt(1) - np.take_along_axis(css, rho[:, None], -1)[:, 0]) \
        / (rho + 1).astype(v.dtype)
    z = v + lam[:, None]
    return np.maximum(z, dt(0)), z


def _near(a, b, tol):
    """Where ``a`` and ``b`` differ, but by less than ``tol`` of ``b``:
    a comparison that rounding could turn.  Exact equality is the same in
    any precision that holds both values, and is not near."""
    with np.errstate(invalid="ignore", divide="ignore"):
        rel = np.abs(a - b) / np.abs(b)
    return (rel > 0) & (rel < tol)


def _top2(score):
    """Indices of the largest and second-largest entries (last axis)."""
    two = np.argpartition(score, -2, axis=-1)[..., -2:]
    vals = np.take_along_axis(score, two, -1)
    order = np.argsort(-vals, axis=-1, kind="stable")
    two = np.take_along_axis(two, order, -1)
    return two[..., 0], two[..., 1]


def _feedback_graph(w, err, costs, budget, w_prev, prev):
    """Algorithm 1 for every lane and source node at once.

    Row ``k`` starts as ``{k}`` and repeatedly appends the eligible node
    maximising ``w_i / (sum of the row's costs + c_i)`` (eq. 3); a node is
    eligible when it is not yet in the row, the row's cost stays within the
    budget and the row's weight within ``w_prev[k]``, last round's row
    ``prev[k]`` under this round's weights (eq. 2).

    Returns the adjacency ``(L, K, K)`` and per lane whether a choice or an
    eligibility test came within reach of rounding: within ``err`` (each weight's
    possible error, see ``run_lanes``) of going the other way."""
    L, K = w.shape
    dt = w.dtype.type
    member = np.broadcast_to(np.eye(K, dtype=bool), (L, K, K)).copy()
    csum = np.broadcast_to(costs, (L, K)).copy()
    wsum = w.copy()
    lim = w_prev * dt(1 + 1e-6)
    near = np.zeros(L, bool)
    lanes, rows = np.indices((L, K))
    # the bound compares weights of two sets: only the weights in one and
    # not the other can be off, and the sums round at about 1e-7
    base = np.sum(np.where(member ^ prev, err[:, None, :], dt(0)), -1)
    for _ in range(K - 1):
        den = csum[:, :, None] + costs[None, None, :]
        fits = ~member & (den <= budget[:, None, None])
        wnew = wsum[:, :, None] + w[:, None, :]
        elig = fits & (wnew <= lim[:, :, None])
        score = np.where(elig, w[:, None, :] / den, dt(-np.inf))
        d = np.argmax(score, axis=-1)
        act = elig.any(-1)
        a, b = _top2(score)
        sa, sb = (np.take_along_axis(score, i[..., None], -1)[..., 0]
                  for i in (a, b))
        da, db = (np.take_along_axis(den, i[..., None], -1)[..., 0]
                  for i in (a, b))
        slack = (np.take_along_axis(err, a, -1) / da
                 + np.take_along_axis(err, b, -1) / db)
        # equal scores come from equal histories, which round alike
        with np.errstate(invalid="ignore"):
            tie = np.isfinite(sb) & (sa > sb) & (sa - sb <= slack)
        spread = (base[:, :, None] + err[:, None, :]
                  * np.where(prev, dt(-1), dt(1)) + dt(1e-7) * np.abs(
                      lim[:, :, None]))
        with np.errstate(invalid="ignore"):
            bound = (fits & np.isfinite(lim)[:, :, None]
                     & (np.abs(wnew - lim[:, :, None]) <= spread))
        near |= np.any(tie | np.any(bound, -1)
                       | np.any(~member & _near(den, budget[:, None, None],
                                                1e-6), -1), -1)
        if not act.any():
            break
        upd = (np.arange(K) == d[..., None]) & act[..., None]
        member |= upd
        base = base + np.sum(np.where(upd, err[:, None, :]
                                      * np.where(prev, dt(-1), dt(1)),
                                      dt(0)), -1)
        csum = csum + np.where(act, costs[d], dt(0))
        wsum = wsum + np.where(act, np.take_along_axis(w, d, -1), dt(0))
    return member, near


def _dominating_set(adj):
    """Greedy set cover (Chvatal): pick the node covering the most
    uncovered nodes, lowest index first, until all are covered."""
    L, K, _ = adj.shape
    dom = np.zeros((L, K), bool)
    unc = np.ones((L, K), bool)
    ai = adj.astype(np.int64)
    lanes = np.arange(L)
    while unc.any():
        gains = np.einsum("lkj,lj->lk", ai, unc.astype(np.int64))
        gains[dom] = -1
        pick = np.argmax(gains, axis=-1)
        live = unc.any(-1)
        dom[lanes[live], pick[live]] = True
        unc &= ~(adj[lanes, pick] & live[:, None])
    return dom


def _softmax_masked(logv, mask):
    dt = logv.dtype.type
    m = np.where(mask, logv, dt(-np.inf))
    top = np.max(m, axis=-1, keepdims=True)
    e = np.where(mask, np.exp(m - top), dt(0))
    return e / np.sum(e, axis=-1, keepdims=True)


def run_lanes(algo: str, preds, y, costs, T: int, budgets, uniforms, *,
              window: int = 5, loss_scale: float = 4.0,
              dtype=np.float64, eps: float = 1e-6) -> dict:
    """Simulate ``L`` lanes of ``algo`` for ``T`` rounds.

    ``preds`` (K, n) are the experts' predictions on the stream, ``y`` (n,)
    its labels, ``costs`` (K,), ``budgets`` (L,), ``uniforms`` (L, T, K) from
    ``draw_uniforms``.  Each round ``window`` clients observe the next
    ``window`` stream samples (wrapping), report their squared-error losses
    normalised by ``loss_scale`` and clipped to 1, and the server updates
    with step ``eta = xi = 1/sqrt(T)``.

    Returns per-round arrays: ``sel`` (L, T, K) transmit sets,
    ``ens_sq_mean`` (the clients' mean squared ensemble error),
    ``resolved``: False where float32 cannot hold the selected experts'
    log-weights finely enough to give the mixture to 1e-5, and
    ``stable``: False in a round where a discrete choice (a greedy append,
    an eligibility test, the draw, FedBoost's inclusion test) came within
    reach of rounding, or where rounding alone decides FedBoost's mixture:
    another correct precision may choose otherwise there, and so may the
    program.  A weight updated ``n`` times may be off by
    ``eps * (|log w| + 1) * sqrt(n)`` of itself (each update rounds at
    about a float32 unit of its log); a weight never updated is exact.
    """
    dt = np.dtype(dtype).type
    K, n = preds.shape
    L = len(budgets)
    preds = np.asarray(preds).astype(dtype)
    y = np.asarray(y).astype(dtype)
    costs = np.asarray(costs).astype(dtype)
    budgets = np.asarray(budgets, np.float64).astype(dtype)
    eta = dt(1.0 / np.sqrt(T))
    ls = dt(loss_scale)
    one = dt(1)
    out = {"sel": np.zeros((L, T, K), bool),
           "ens_sq_mean": np.zeros((L, T)),
           "stable": np.ones((L, T), bool),
           "resolved": np.ones((L, T), bool)}
    lanes = np.arange(L)
    if algo == "eflfg":
        log_w = np.zeros((L, K), dtype)
        log_u = np.zeros((L, K), dtype)
        w_prev = np.full((L, K), np.inf, dtype)
        prev = np.zeros((L, K, K), bool)
        updates = np.zeros((L, K))
        gumbel = -np.log(-np.log(np.asarray(uniforms, np.float64))).astype(
            dtype)
    elif algo == "fedboost":
        alpha = np.full((L, K), one / dt(K), dtype)
        z = alpha
        pi = np.minimum(one, budgets / np.sum(costs))[:, None]
    else:
        raise ValueError(f"unknown algo {algo!r}")
    for t in range(T):
        if algo == "eflfg":
            w = np.exp(log_w)
            err = w * (eps * (np.abs(log_w.astype(np.float64)) + 1)
                       * np.sqrt(updates)).astype(dtype)
            adj, near = _feedback_graph(w, err, costs, budgets, w_prev,
                                        prev)
            prev = adj
            dom = _dominating_set(adj)
            exploit = _softmax_masked(log_u, np.ones_like(dom))
            explore = dom.astype(dtype) / dom.sum(-1, keepdims=True).astype(
                dtype)
            p = (one - eta) * exploit + eta * explore
            p = p / np.sum(p, -1, keepdims=True)
            key = gumbel[:, t] + np.log(np.maximum(p, dt(1e-38)))
            drawn = np.argmax(key, axis=-1)
            a, b = _top2(key)
            near |= (np.take_along_axis(key, a[:, None], -1)
                     - np.take_along_axis(key, b[:, None], -1))[:, 0] < 1e-5
            sel = adj[lanes, drawn]
            mix = _softmax_masked(log_w, sel)
            # float32 holds a log-weight to 2**-23 of its size: past
            # |log w| = 80 it cannot give the mixture to 1e-5
            out["resolved"][:, t] = np.max(np.where(
                sel, np.abs(log_w), dt(0)), -1) * 2.0**-23 < 1e-5
            out["stable"][:, t] = ~near
        else:
            u = np.asarray(uniforms[:, t])
            sel = u < pi
            near = np.any(_near(u, pi, 1e-6), -1)
            none = ~sel.any(-1)
            sel[lanes[none], np.argmax(alpha[none], -1)] = True
            a, b = _top2(alpha)
            lead = (np.take_along_axis(alpha, a[:, None], -1)
                    - np.take_along_axis(alpha, b[:, None], -1))[:, 0]
            near |= none & (lead > 0) & (lead < 1e-5)
            masked = np.where(sel, alpha, dt(0))
            mix = masked / np.maximum(np.sum(masked, -1, keepdims=True),
                                      dt(1e-12))
            # the mixture divides the selected weights by their sum: when
            # that sum is small, rounding decides the mixture unless one
            # selected weight holds it all and no other sits within
            # rounding of the simplex's edge
            edge = np.any(sel & (np.abs(z) < dt(1e-6)), -1)
            several = np.sum(sel & (alpha > 0), -1) > 1
            out["stable"][:, t] = ~near & ((np.sum(masked, -1) >= dt(1e-3))
                                           | ~(edge | several))
        idx = (t * window + np.arange(window)) % n
        p_cl = preds[:, idx]                                # (K, W)
        y_cl = y[idx]                                       # (W,)
        sq = (p_cl - y_cl) ** 2
        ml = np.sum(np.minimum(sq / ls, one), axis=-1)      # (K,)
        yhat = np.sum(mix[:, :, None] * p_cl[None], axis=1)  # (L, W)
        e2 = (yhat - y_cl) ** 2
        ens_loss = np.sum(np.minimum(e2 / ls, one), -1)
        out["sel"][:, t] = sel
        out["ens_sq_mean"][:, t] = np.mean(e2, -1)
        if algo == "eflfg":
            q = np.sum(p[:, :, None] * adj, axis=1)
            ell = np.where(sel, ml / np.maximum(q, dt(1e-12)), dt(0))
            p_drawn = p[lanes, drawn]
            log_w = log_w - eta * ell
            updates += sel
            log_u[lanes, drawn] -= eta * ens_loss / np.maximum(p_drawn,
                                                               dt(1e-12))
            w_prev = np.sum(np.where(adj, np.exp(log_w)[:, None, :],
                                     dt(0)), axis=-1)
        else:
            grad = dt(2.0 / window) * np.sum(
                p_cl[None] * (yhat - y_cl)[:, None, :], -1)
            g = np.where(sel, grad / pi, dt(0))
            alpha, z = project_simplex(alpha - eta * g)
    return out
