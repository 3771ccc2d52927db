"""The main path compiles for a TPU v5e, without a chip.

The TPU compiler is installed with JAX; it compiles for a *described*
``v5e:2x2`` topology.  These tests compile, with ``interpret=False``, at
the paper's largest shapes (the ``energy`` stream: K=22 experts,
n_stream=17,761) with the default window W=5 and the bandwidth-formula
window W=100:

* ``client_eval`` in its three variants (with / without the FedBoost
  gradient, and with the scenario schedule operands);
* ``server_plan`` and ``server_update``, and the EFL-FG scan through
  them, with the float32 ``exp`` / ``log`` polynomials of
  ``repro.core.numerics`` inside the Mosaic kernels;
* the default (fused) EFL-FG scan, solo and as a 16-lane flat vmap;
* the FedBoost budget x seed grid sweep, whose per-round transmit sets
  must be stacked with the round axis major.

Each asserts that a Mosaic kernel (``tpu_custom_call``) is in the
compiled program and reads its ``memory_analysis()``.  A compile that
passes is not a chip run: nothing executes here.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports every test file.
"""

import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.federated import SimConfig, engine
from repro.kernels.client_eval import ops as ce_ops
from repro.kernels.client_eval.kernel import client_eval_pallas
from repro.kernels.client_eval.ref import extended_width
from repro.kernels.server_round import ops as sr_ops
from repro.kernels.server_round.kernel import (server_plan_pallas,
                                               server_update_pallas)

K, N_STREAM, T = 22, 17_761, 3000


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        # keep the TPU compiler's logs off the filesystem
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        from jax.experimental import topologies
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:          # noqa: BLE001 — any failure skips
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache without one: keep these out of it
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The ops see the CPU backend here; steer them to the compiled
    (``interpret=False``) kernels, as on a TPU."""
    monkeypatch.setattr(ce_ops, "interpret_mode", lambda: False)
    monkeypatch.setattr(sr_ops, "interpret_mode", lambda: False)


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_check(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem is not None and mem.argument_size_in_bytes > 0
    return compiled


@pytest.mark.parametrize("window", [5, 100])
@pytest.mark.parametrize("variant", ["grad", "no_grad", "scheduled"])
def test_client_eval_compiles(one_chip, window, variant):
    L = extended_width(N_STREAM, window)
    s = lambda *a: _sds(one_chip, *a)
    args = [s((K, L)), s((L,)), s((), jnp.int32), s((), jnp.int32),
            s((K,))]
    kw = dict(loss_scale=4.0, window=window, interpret=False,
              with_grad=variant == "grad")
    if variant == "scheduled":
        args += [s((window,), jnp.bool_), s(())]

        def fn(*a):
            return client_eval_pallas(*a[:5], active=a[5], shift=a[6], **kw)
    else:
        def fn(*a):
            return client_eval_pallas(*a, **kw)
    _compile_check(fn, *args)


def test_server_plan_compiles(one_chip):
    s = lambda *a: _sds(one_chip, *a)
    _compile_check(lambda *a: server_plan_pallas(*a, interpret=False),
                   s((K,)), s((K,)), s((K, K), jnp.bool_), s((K,)), s(()),
                   s((K,)), s(()))


def test_server_update_compiles(one_chip):
    s = lambda *a: _sds(one_chip, *a)
    _compile_check(lambda *a: server_update_pallas(*a, interpret=False),
                   s((K, K), jnp.bool_), s((K,)), s((K,), jnp.bool_),
                   s((), jnp.int32), s((K,)), s(()), s((K,)), s((K,)),
                   s(()))


def test_fused_server_scan_compiles(one_chip, compiled_kernels):
    """The EFL-FG scan with both rounds' halves in Mosaic kernels
    (``use_fused_server``): a bitcast in ``numerics``' ``exp`` / ``log``
    takes vectors only there."""
    scan = engine._make_scan("eflfg", T, SimConfig(use_fused_server=True))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    s = lambda *a: _sds(one_chip, *a)
    _compile_check(scan, s((K, N_STREAM)), s((N_STREAM,)), s((K,)),
                   s(key.shape, key.dtype), s(()))


@pytest.mark.parametrize("lanes", [None, 16])
def test_fused_eflfg_scan_compiles(one_chip, compiled_kernels, lanes):
    """The default ``SimConfig`` (fused client eval) EFL-FG scan, solo and
    as the serving layer's flat vmap."""
    scan = engine._make_scan("eflfg", T, SimConfig())
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    s = lambda *a: _sds(one_chip, *a)
    stream = (s((K, N_STREAM)), s((N_STREAM,)), s((K,)))
    if lanes is None:
        _compile_check(scan, *stream, s(key.shape, key.dtype), s(()))
    else:
        def flat(p, y, c, keys, budgets):
            return jax.vmap(lambda k, b: scan(p, y, c, k, b))(keys, budgets)
        _compile_check(flat, *stream, s((lanes,) + key.shape, key.dtype),
                       s((lanes,)))


def test_grid_sweep_stacks_transmit_sets_round_major(
        one_chip, compiled_kernels, monkeypatch):
    """The benchmark's sweep (FedBoost, 4 budgets x 10 seeds, energy
    shapes): a pred stack of the rounds' transmit sets would be laid out
    with the round axis minor-most, and written one strided column per
    round.  The packed uint32 word stack takes the float stacks' layout."""
    monkeypatch.setattr(engine, "_SCAN_CACHE", {})
    fn = engine._get_scan("fedboost", T, SimConfig(), sweep="grid")
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    s = lambda *a: _sds(one_chip, *a)
    text = fn.lower(s((K, N_STREAM)), s((N_STREAM,)), s((K,)),
                    s((10,) + key.shape, key.dtype),
                    s((4,))).compile().as_text()
    writes = [ln for ln in text.splitlines() if "dynamic-update-slice" in ln]
    assert not [ln for ln in writes if f"= pred[{T}," in ln]
    # the sel stack: one word per (round, budget, seed), minor_to_major
    # ending in dim 0 (rounds)
    sel = re.findall(rf"= u32\[{T},4,10\]\{{([0-9,]+)", "\n".join(writes))
    assert sel and all(m.split(",")[-1] == "0" for m in sel)
