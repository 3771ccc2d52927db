"""Where the Pallas kernels run: compiled on a TPU, interpreted on the CPU.

Every kernel op asks ``interpret_mode()`` instead of testing "not TPU":
a backend that is neither (a GPU, or a TPU plugin that failed and left
some other default) raises ``UnsupportedPlatformError`` rather than
quietly running the interpreter and passing for a chip run.

``prepare_mosaic()`` is called by the kernel wrappers before a compiled
(``interpret=False``) launch.  The kernel bodies reach
``lax.optimization_barrier`` through the ladder reductions of
``repro.core.numerics``; the barrier only steers XLA's own pipeline (it
is an identity on values), and the Mosaic TPU lowering has no rule for
it.  Mosaic runs no XLA algebraic simplifier, so the identity rule
registered here is the barrier's exact meaning inside a kernel.
"""

from __future__ import annotations

import functools

import jax

__all__ = ["MosaicRuleError", "UnsupportedPlatformError", "interpret_mode",
           "prepare_mosaic"]


class UnsupportedPlatformError(RuntimeError):
    """The default JAX backend is neither ``tpu`` nor ``cpu``."""


class MosaicRuleError(RuntimeError):
    """The installed JAX has no Mosaic lowering-rule table where
    ``prepare_mosaic`` expects it."""


def interpret_mode() -> bool:
    """``False`` on a TPU (compiled Mosaic), ``True`` on the CPU (the
    Pallas interpreter, the test vehicle); any other backend raises."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise UnsupportedPlatformError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the default JAX backend is {platform!r}")


@functools.cache
def prepare_mosaic() -> None:
    """Give the Mosaic TPU lowering an identity rule for
    ``optimization_barrier`` (once per process).

    The rule table is private to JAX (``jax._src.pallas.mosaic.lowering``,
    as of the pinned jax 0.9.0).  If a JAX release moves it, this raises
    ``MosaicRuleError`` here instead of failing deep inside a kernel's
    lowering; if a release brings its own rule, that one is kept."""
    barrier = jax.lax.optimization_barrier_p
    try:
        from jax._src.pallas.mosaic import lowering as mosaic
        rules = mosaic.lowering_rules[mosaic.tpu_core.KernelType.TC]
        if barrier not in rules:
            mosaic.register_lowering_rule(barrier)(
                lambda ctx, *args: list(args))
    except (ImportError, AttributeError, KeyError, TypeError) as e:
        raise MosaicRuleError(
            f"jax {jax.__version__} moved the Mosaic TPU lowering-rule table "
            f"this module extends (written against jax 0.9.0): {e!r}") from e
    if barrier not in rules:
        raise MosaicRuleError(
            f"jax {jax.__version__}: no Mosaic TPU lowering rule for "
            f"{barrier.name} after registering one")
