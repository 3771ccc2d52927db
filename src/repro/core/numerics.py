"""Fixed-structure reductions for cross-program bit-reproducibility.

XLA never *reassociates* float arithmetic, but a ``reduce`` op's
accumulation order is an emitter choice — and the choice depends on the
fusion context the reduce lands in.  Two programs computing the same
``jnp.sum`` over the same values can therefore disagree by an ulp (CPU
SIMD lane splits differ between fusion clusters).  That is invisible in
a single program, but it breaks the library's strongest contract: the
fused Pallas server round (``repro.kernels.server_round``) must produce
*bit-equal* trajectories to the unfused scan, and an ulp in any quantity
that feeds back through the weight state eventually flips a discrete
selection (empirically by round ~400 at paper scale).

The ladder reductions here remove the emitter's freedom: the summation
tree is spelled out as explicit slice-halving adds (pad to a power of
two with the identity, then fold high half onto low half).  Explicit
adds have a defined order in HLO, so every program — unfused scan,
interpret-mode Pallas kernel, vmapped sweep — accumulates identically.
Zero-padding is exact for sums (x + 0.0 == x for every finite x and
inf; only -0.0 is normalized to +0.0, and none of our summands carry a
meaningful negative zero).

Cost: a K-vector sum becomes ceil(log2 K) vector adds instead of one
reduce — noise for the K=22 server quantities these guard.  Integer and
boolean reductions (``sum(dom)``, ``any``) and pure ``max``/``argmax``
reductions are order-independent already and do not need this.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["ladder_sum", "ladder_logsumexp", "ladder_matvec",
           "rounding_barrier", "fma_fence", "exp", "log", "gumbel"]


def rounding_barrier(x: jnp.ndarray) -> jnp.ndarray:
    """Identity that discourages FMA contraction across it (best effort).

    XLA's backends may contract a ``mul`` feeding an ``add``/``sub`` into
    an FMA — per fusion cluster, so two programs computing the same
    ``a - b * c`` can disagree by an ulp.  An ``optimization_barrier``
    around the product keeps HLO passes from fusing mul and add into one
    cluster... usually.  The barrier is *expanded away* late in the XLA
    pipeline, and empirically (CPU) the vmapped interpret-mode Pallas
    grid program still contracts straight through it — the recorded
    product is rounded once while the consuming ``sub`` sees the
    unrounded product.  Where the contraction provably flips downstream
    selections, use ``fma_fence`` instead; this barrier remains on the
    ladder inputs as cheap extra friction.  Inside a compiled TPU kernel
    the barrier lowers to an identity (``repro.kernels.platform``).
    """
    return jax.lax.optimization_barrier(x)


def fma_fence(x: jnp.ndarray) -> jnp.ndarray:
    """Force ``x`` (typically a fresh product) to round before its
    consumer: a *division* output cannot be FMA-contracted.

    ``x / ((|x| + 1) / (|x| + 1))`` is bit-exact identity for every
    finite ``x``: ``|x| + 1`` is finite and >= 1, so ``a / a`` is exactly
    1.0 and ``x / 1.0 == x``.  No compiler may fold it — proving
    ``a / a == 1`` is unsound under IEEE (NaN/inf/0 operands), and the
    anchor is runtime data, never a foldable constant.  Unlike
    ``rounding_barrier`` this survives the whole pipeline: there is no
    fused divide-add instruction, so the consumer of the fence output
    must take the once-rounded value in every fusion context (flat scan,
    vmapped sweep, interpret-mode Pallas grid).  Cost: four elementwise
    ops.  Caveats: an *infinite* ``x`` comes back NaN (inf/inf anchor),
    and a *subnormal* ``x`` flushes to (signed) zero under XLA CPU's
    FTZ environment — deterministically, in every program variant, and
    a subnormal eq.-(4)/(9) product is semantically zero anyway.  Fence
    only quantities with bounded magnitude, like the products this
    guards.

    The inner division hides behind ``rounding_barrier`` for a different
    reason than FMA: the HLO algebraic simplifier rewrites
    ``x / (a / a)`` into ``(x * a) / a`` (div-of-div), which double-
    rounds and overflows for large ``x``.  The simplifier does respect
    barriers, and LLVM never reassociates divisions, so the exposed
    shape is exactly ``x / t`` with ``t == 1.0``.
    """
    a = jnp.abs(x) + 1.0
    return x / rounding_barrier(a / a)


def ladder_sum(x: jnp.ndarray, axis: int = -1, *,
               fence: bool = True) -> jnp.ndarray:
    """Sum over ``axis`` with a fixed pairwise-halving add tree.

    The input rides through ``fma_fence`` first: when the summands are
    fresh products (``ladder_matvec``, masked squared errors) the tree's
    first add level would otherwise be FMA-contractible, re-introducing
    exactly the per-program rounding freedom the ladder exists to remove
    — empirically the shard_map-partitioned sweep contracts where the
    equal-width vmap program does not, drifting the loss curves between
    the two (a plain ``rounding_barrier`` here does not survive every
    backend pipeline; see ``fma_fence``).  The fence's caveats apply:
    summands must be finite (an ``inf`` comes back NaN), and subnormal
    summands flush to zero under XLA CPU's FTZ environment —
    deterministically, in every program variant.  ``fence=False`` skips
    the fence where no summand is a product (masked selects of stored
    values), on hot loops that cannot spare its four ops."""
    x = jnp.moveaxis(x, axis, -1)
    if fence:
        x = fma_fence(x)
    n = x.shape[-1]
    p = 1 << max(n - 1, 0).bit_length()        # next power of two
    if p != n:
        pad = [(0, 0)] * (x.ndim - 1) + [(0, p - n)]
        x = jnp.pad(x, pad)
    while x.shape[-1] > 1:
        h = x.shape[-1] // 2
        x = x[..., :h] + x[..., h:]
    return x[..., 0]


# ---------------------------------------------------------------------------
# float32 exp / log to within a few ulp on every backend
# ---------------------------------------------------------------------------
#
# A TPU's own float32 ``exp`` is up to 4.5e-6 off (relative, v5 lite) and
# its ``log`` up to 1e-4 (absolute): EFL-FG's eq.-(5) mixture and its
# draw's keys then move by ~1e-4, where the algorithms' comparisons
# resolve 1e-6 to 1e-5.  The round takes these polynomials on every
# backend instead, so the host runs what the chip runs.

_LOG2E = 1.4426950408889634
_LN2_HI = 0.693145751953125          # 16 significant bits: n * hi is exact
_LN2_LO = 1.4286068203094172e-06
_SQRT2 = 1.4142135
_TINY = 1.1754944e-38                # the smallest normal float32


def _exp_f32(x):
    """``exp`` from float32 arithmetic alone: ``x = n ln2 + r`` (Cody-Waite),
    ``exp(r)`` by its degree-7 Taylor polynomial on ``|r| <= ln2 / 2``
    (truncation 7e-9), ``2^n`` through the exponent bits: within 1.3 ulp
    up to ``x = 88`` (10 ulp above, where ``n`` is clipped).  Flushes
    below the smallest normal, as the TPU does."""
    x = jnp.asarray(x, jnp.float32)
    n = jnp.clip(jnp.floor(x * _LOG2E + 0.5), -126.0, 127.0)
    r = (x - n * _LN2_HI) - n * _LN2_LO
    p = jnp.float32(1.0 / 5040.0)
    for c in (1 / 720, 1 / 120, 1 / 24, 1 / 6, 0.5, 1.0, 1.0):
        p = p * r + c
    scale = jax.lax.bitcast_convert_type(
        (n.astype(jnp.int32) + 127) << 23, jnp.float32)
    out = jnp.where(x < -87.33, 0.0, p * scale)
    out = jnp.where(x > 88.72, jnp.inf, out)
    return jnp.where(jnp.isnan(x), x, out)


def _log_f32(x):
    """``log`` from float32 arithmetic alone: ``x = m 2^e`` with ``m`` in
    ``[sqrt(1/2), sqrt(2))``, ``log m = 2 atanh(s)``, ``s = (m-1)/(m+1)``,
    by its series to ``s^11`` (``|s| <= 0.172``, truncation 2e-11).
    Subnormal inputs count as 0, as the TPU flushes them."""
    x = jnp.asarray(x, jnp.float32)
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    e = ((bits >> 23) & 0xFF) - 127
    m = jax.lax.bitcast_convert_type((bits & 0x7FFFFF) | 0x3F800000,
                                     jnp.float32)
    big = m > _SQRT2
    m = jnp.where(big, m * 0.5, m)
    e = jnp.where(big, e + 1, e).astype(jnp.float32)
    s = (m - 1.0) / (m + 1.0)
    s2 = s * s
    p = jnp.float32(1.0 / 11.0)
    for c in (1 / 9, 1 / 7, 1 / 5, 1 / 3, 1.0):
        p = p * s2 + c
    out = e * _LN2_HI + (e * _LN2_LO + 2.0 * s * p)
    out = jnp.where(x < _TINY, -jnp.inf, out)
    out = jnp.where(x == jnp.inf, jnp.inf, out)
    return jnp.where(jnp.isnan(x) | (x < 0), jnp.nan, out)


def _on_vector(f, x):
    """``f`` on ``x``, a scalar lifted to a 1-vector first: Mosaic bitcasts
    vectors only."""
    x = jnp.asarray(x, jnp.float32)
    return f(jnp.broadcast_to(x, (1,)))[0] if x.ndim == 0 else f(x)


def exp(x: jnp.ndarray) -> jnp.ndarray:
    """``jnp.exp``, to within a few float32 ulp on every backend."""
    return _on_vector(_exp_f32, x)


def log(x: jnp.ndarray) -> jnp.ndarray:
    """``jnp.log``, to within a few float32 ulp on every backend."""
    return _on_vector(_log_f32, x)


def gumbel(key, shape) -> jnp.ndarray:
    """``jax.random.gumbel(key, shape, float32)`` with ``log`` above: the
    same uniforms on ``[tiny, 1)``."""
    u = jax.random.uniform(key, shape, jnp.float32,
                           minval=jnp.finfo(jnp.float32).tiny, maxval=1.0)
    return -log(-log(u))


def ladder_logsumexp(x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Max-shifted logsumexp whose inner sum is a ``ladder_sum``.

    Matches ``jax.scipy.special.logsumexp`` semantics for the library's
    inputs (max over ``axis`` is order-independent bit-for-bit, the
    shift keeps ``exp`` in range; masked entries ride as large-negative
    sentinels, never a full row of them).
    """
    m = jnp.max(x, axis=axis, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)     # all-masked-row guard
    s = ladder_sum(exp(x - m), axis=axis)
    return log(s) + jnp.squeeze(m, axis=axis)


def ladder_matvec(v: jnp.ndarray, m: jnp.ndarray) -> jnp.ndarray:
    """``v @ m`` ((K,) @ (K, N)) as elementwise products + ladder_sum."""
    return ladder_sum(v[..., :, None] * m, axis=-2)
