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
           "rounding_barrier", "fma_fence"]


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


def ladder_sum(x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
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
    deterministically, in every program variant."""
    x = fma_fence(jnp.moveaxis(x, axis, -1))
    n = x.shape[-1]
    p = 1 << max(n - 1, 0).bit_length()        # next power of two
    if p != n:
        pad = [(0, 0)] * (x.ndim - 1) + [(0, p - n)]
        x = jnp.pad(x, pad)
    while x.shape[-1] > 1:
        h = x.shape[-1] // 2
        x = x[..., :h] + x[..., h:]
    return x[..., 0]


def ladder_logsumexp(x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Max-shifted logsumexp whose inner sum is a ``ladder_sum``.

    Matches ``jax.scipy.special.logsumexp`` semantics for the library's
    inputs (max over ``axis`` is order-independent bit-for-bit, the
    shift keeps ``exp`` in range; masked entries ride as large-negative
    sentinels, never a full row of them).
    """
    m = jnp.max(x, axis=axis, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)     # all-masked-row guard
    s = ladder_sum(jnp.exp(x - m), axis=axis)
    return jnp.log(s) + jnp.squeeze(m, axis=axis)


def ladder_matvec(v: jnp.ndarray, m: jnp.ndarray) -> jnp.ndarray:
    """``v @ m`` ((K,) @ (K, N)) as elementwise products + ladder_sum."""
    return ladder_sum(v[..., :, None] * m, axis=-2)
