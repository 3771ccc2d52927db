"""Tests for the dry-run analysis stack: weighted HLO parsing, chunked CE
parity, roofline math, shard context."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp


def test_hloparse_counts_scan_trips():
    """Weighted dot flops must equal trips x body flops (XLA reports the
    body once)."""
    from repro.launch.hloparse import analyze_hlo
    L, n, b = 5, 64, 4

    def f(ws, x):
        def body(x, w):
            return jnp.tanh(x @ w), None
        x, _ = jax.lax.scan(body, x, ws)
        return x.sum()

    ws = jnp.zeros((L, n, n))
    x = jnp.ones((b, n))
    compiled = jax.jit(f).lower(ws, x).compile()
    res = analyze_hlo(compiled.as_text())
    expected = 2 * b * n * n * L
    assert res["dot_flops"] == expected, (res["dot_flops"], expected)
    reported = compiled.cost_analysis().get("flops", 0)
    assert reported < expected  # the very bug the parser fixes


def test_hloparse_shape_bytes():
    from repro.launch.hloparse import _shape_bytes
    assert _shape_bytes("bf16[4,8]") == 64
    assert _shape_bytes("f32[10]") == 40
    assert _shape_bytes("(f32[2,2], s32[3])") == 28
    assert _shape_bytes("pred[]") == 1


def test_chunked_ce_matches_full():
    from repro.models import get_config, model
    from repro.data import TokenStream
    cfg = get_config("qwen3-1.7b").reduced(n_layers=1, vocab_size=768)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    ts = TokenStream(cfg.vocab_size, batch=2, seq_len=48)
    b = ts.batch_at(0)
    full, _ = model.loss_fn(cfg, params, b)
    chunked, _ = model.loss_fn(cfg, params, b, ce_chunk=16)
    np.testing.assert_allclose(float(full), float(chunked), rtol=2e-5)
    # gradients agree too (checkpointed backward)
    g1 = jax.grad(lambda p: model.loss_fn(cfg, p, b)[0])(params)
    g2 = jax.grad(lambda p: model.loss_fn(cfg, p, b, ce_chunk=16)[0])(params)
    for a, c in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   atol=1e-5, rtol=1e-4)


def test_shardctx_noop_without_mesh():
    from repro.models import shardctx
    shardctx.clear_ctx()
    x = jnp.ones((2, 4, 8, 16))
    assert shardctx.constrain_bshd(x) is x
    assert shardctx.constrain_bsd(jnp.ones((2, 4, 8))) is not None


def test_roofline_model_flops():
    import sys, os
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.roofline import model_flops
    from repro.models import get_config
    # dense train: 6 N D
    n = get_config("qwen3-1.7b").n_params()
    assert model_flops("qwen3-1.7b", "train_4k") == pytest.approx(
        6.0 * n * 256 * 4096)
    # MoE uses active params
    cfg = get_config("mixtral-8x22b")
    assert model_flops("mixtral-8x22b", "decode_32k") == pytest.approx(
        2.0 * cfg.n_active_params() * 128)


def test_dryrun_skips_recorded():
    from repro.launch.specs import SKIPS, dryrun_pairs
    pairs = dryrun_pairs()
    assert ("whisper-tiny", "train_4k") in pairs
    assert ("whisper-tiny", "decode_32k") not in pairs
    assert len(pairs) == 10 * 4 - len(SKIPS)
