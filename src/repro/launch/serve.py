"""Serving drivers: multi-tenant simulation serving + the LM decode demo.

Two subcommands:

``simulate`` — the production-shaped driver for ``repro.serve``: spins
up a ``SimServer``, registers a synthetic expert stream, fires a wave of
concurrent simulation requests (mixed seeds/budgets/algorithms) from
client threads, and reports request throughput, batch occupancy and
executable-cache behavior.  ``--verify N`` cross-checks N served
results against direct engine runs.

``decode`` — the original token-decode demo (batched prefill + decode
loop with KV caches) on a reduced LM architecture.

    PYTHONPATH=src python -m repro.launch.serve simulate \
        --requests 32 --algos eflfg,fedboost --T 2000
    PYTHONPATH=src python -m repro.launch.serve decode \
        --arch qwen3-1.7b --batch 4 --prompt-len 64 --gen 32

See docs/serving.md for the serving architecture and tuning guide.
"""

from __future__ import annotations

import argparse
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp

_EPILOG = """\
subcommand details:

  simulate   serve a wave of concurrent EFL-FG / FedBoost simulation
             requests through repro.serve's dynamic batcher.  Requests
             cycle through --algos with seeds 0..N-1 and budgets from
             --budgets; --exact switches every request to the
             bit-reproducible exact mode; --serial disables batching
             (direct per-request engine calls) for an A/B throughput
             comparison.  Reports req/s, batch occupancy, padding and
             cache hits/misses.
  decode     the LM serving demo this module used to be: batched
             prefill then a decode loop with KV caches on a reduced
             architecture (--arch/--batch/--prompt-len/--gen).

docs/serving.md documents the request lifecycle, bucketing rules,
determinism guarantees and the latency/throughput tuning knobs.
"""


# ---------------------------------------------------------------------------
# simulate: multi-tenant simulation serving
# ---------------------------------------------------------------------------

def _synthetic_stream(K: int, n_stream: int, seed: int = 1):
    rng = np.random.default_rng(seed)
    return (rng.normal(0, 1, (K, n_stream)).astype(np.float32),
            rng.normal(0, 1, n_stream).astype(np.float32),
            rng.uniform(0.05, 1.0, K).astype(np.float32))


def simulate(n_requests: int = 32, algos=("eflfg", "fedboost"), *,
             T: int = 2000, K: int = 22, n_clients: int = 100,
             budgets=(3.0,), use_fused: bool = False, exact: bool = False,
             serial: bool = False, max_batch: int = 16,
             max_wait_ms: float = 2.0, threads: int = 4,
             n_stream: int = 6000, verify: int = 0, seed: int = 1) -> dict:
    """Serve ``n_requests`` mixed simulation requests; return metrics.

    ``serial=True`` is the A/B baseline: the same requests as direct
    sequential engine calls (no server).  ``use_fused`` defaults off —
    the serving default for batched CPU traffic, where the unfused round
    body vectorizes across lanes (docs/serving.md#tuning).
    """
    from repro.federated import SimConfig, run_simulation_scan
    from repro.serve import SimServer, SimClient

    preds, y, costs = _synthetic_stream(K, n_stream, seed)
    cfg = SimConfig(n_clients=n_clients, use_fused=use_fused)
    specs = [dict(algo=algos[i % len(algos)], seed=i, T=T,
                  budget=float(budgets[i % len(budgets)]), cfg=cfg,
                  exact=exact)
             for i in range(n_requests)]

    if serial:
        from dataclasses import replace
        t0 = time.time()
        results = [run_simulation_scan(
            s["algo"], preds, y, costs, T,
            replace(cfg, seed=s["seed"], budget=s["budget"]))
            for s in specs]
        elapsed = time.time() - t0
        return {"mode": "serial", "requests": n_requests,
                "elapsed_s": elapsed, "req_per_s": n_requests / elapsed,
                "results": results}

    server = SimServer(max_batch=max_batch, max_wait_ms=max_wait_ms)
    server.register_stream("default", preds, y, costs)
    client = SimClient(server)
    futs, errors, lock = [], [], threading.Lock()
    chunks = [specs[i::threads] for i in range(threads)]

    def submit_chunk(chunk):
        try:
            mine = client.submit_many(chunk)
        except Exception as exc:                    # noqa: BLE001
            with lock:
                errors.append(exc)
            return
        with lock:
            futs.extend(mine)

    # the server runs WHILE clients submit — max_wait_ms/threads really
    # shape the batching here (the bench pre-queues instead, for
    # deterministic bucket shapes; see engine_bench._serve_record)
    t0 = time.time()
    server.start()
    workers = [threading.Thread(target=submit_chunk, args=(c,))
               for c in chunks if c]
    for w in workers:
        w.start()
    for w in workers:
        w.join()
    if errors:
        server.stop()
        raise errors[0]
    results = [f.result(3600) for f in futs]
    elapsed = time.time() - t0
    server.stop()

    n_verified = 0
    if verify:
        from dataclasses import replace
        from repro.federated import run_batch
        for f, res in list(zip(futs, results))[:verify]:
            r = f.request
            b = r.budget if r.budget is not None else cfg.budget
            if exact:
                # exact mode: bit-equal to a direct solo engine run
                direct = run_simulation_scan(
                    r.algo, preds, y, costs, T,
                    replace(cfg, seed=r.seed, budget=b))
            else:
                # batched mode: bit-equal to the batched program family —
                # a width-2 run_batch of the same config reproduces any
                # bucket's bits for it (width invariance,
                # docs/serving.md#determinism)
                direct = run_batch(r.algo, preds, y, costs, T, cfg,
                                   seeds=[r.seed, r.seed],
                                   budgets=[b, b])[0]
            if not res.identical_to(direct):
                raise AssertionError(
                    f"verify failed for {r.algo}/seed={r.seed} "
                    f"(exact={exact}; see docs/serving.md#determinism)")
            n_verified += 1
    return {"mode": "exact" if exact else "batched",
            "requests": n_requests, "elapsed_s": elapsed,
            "req_per_s": n_requests / elapsed, "verified": n_verified,
            "stats": server.stats(), "results": results}


def _cmd_simulate(a) -> None:
    rep = simulate(a.requests, tuple(a.algos.split(",")), T=a.T, K=a.K,
                   n_clients=a.n_clients,
                   budgets=tuple(float(b) for b in a.budgets.split(",")),
                   use_fused=a.fused, exact=a.exact, serial=a.serial,
                   max_batch=a.max_batch, max_wait_ms=a.max_wait_ms,
                   threads=a.threads, verify=a.verify)
    print(f"{rep['mode']}: {rep['requests']} requests in "
          f"{rep['elapsed_s']:.3f}s = {rep['req_per_s']:.1f} req/s")
    if "stats" in rep:
        st = rep["stats"]
        occ = st["mean_occupancy"]
        print(f"batches {st['batches']}, occupancy "
              f"{occ if occ is None else round(occ, 3)}, padded lanes "
              f"{st['padded_lanes']}, sharded batches "
              f"{st['sharded_batches']}, cache {st['cache']}")
    if rep.get("verified"):
        print(f"verified {rep['verified']} served results against direct "
              "engine runs")


# ---------------------------------------------------------------------------
# decode: the LM prefill+decode demo
# ---------------------------------------------------------------------------

def serve(arch: str, *, batch=4, prompt_len=64, gen=32, layers=2,
          d_model=256, vocab=2048, temperature=0.0, seed=0):
    """Batched prefill + decode loop with KV caches (reduced LM)."""
    from repro.models import get_config, model
    from repro.data import TokenStream

    cfg = get_config(arch).reduced(n_layers=layers, d_model=d_model,
                                   vocab_size=vocab)
    params = model.init_params(cfg, jax.random.PRNGKey(seed))
    ts = TokenStream(cfg.vocab_size, batch=batch, seq_len=prompt_len,
                     seed=seed)
    prompts = ts.batch_at(0).tokens

    cache_len = prompt_len + gen
    caches = model.init_cache(cfg, batch, cache_len)

    prefill = jax.jit(lambda p, c, t: model.prefill(cfg, p, c, t))
    decode = jax.jit(lambda p, c, t, pos: model.decode_step(cfg, p, c, t,
                                                            pos))

    t0 = time.time()
    logits, caches = prefill(params, caches, prompts)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    key = jax.random.PRNGKey(seed + 1)
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    out_tokens = [tok]
    t0 = time.time()
    for i in range(gen - 1):
        logits, caches = decode(params, caches, tok,
                                jnp.int32(prompt_len + i))
        if temperature > 0:
            key, k = jax.random.split(key)
            tok = jax.random.categorical(
                k, logits[:, -1] / temperature)[:, None].astype(jnp.int32)
        else:
            tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0
    gen_toks = np.concatenate([np.asarray(t) for t in out_tokens], axis=1)
    return {
        "prefill_s": t_prefill,
        "decode_tok_s": batch * (gen - 1) / t_decode if gen > 1 else 0.0,
        "generated": gen_toks,
    }


def _cmd_decode(a) -> None:
    res = serve(a.arch, batch=a.batch, prompt_len=a.prompt_len, gen=a.gen,
                layers=a.layers, d_model=a.d_model,
                temperature=a.temperature)
    print(f"prefill {res['prefill_s']*1e3:.1f} ms, "
          f"decode {res['decode_tok_s']:.1f} tok/s (batched)")
    print("sample tokens:", res["generated"][0][:16].tolist())


def main():
    ap = argparse.ArgumentParser(
        prog="repro.launch.serve",
        description="Serving drivers: multi-tenant simulation serving "
                    "(repro.serve) and the LM decode demo.",
        epilog=_EPILOG,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = ap.add_subparsers(dest="cmd", required=True)

    sim = sub.add_parser(
        "simulate", help="serve concurrent simulation requests")
    sim.add_argument("--requests", type=int, default=32)
    sim.add_argument("--algos", default="eflfg,fedboost",
                     help="comma list cycled over requests")
    sim.add_argument("--T", type=int, default=2000)
    sim.add_argument("--K", type=int, default=22)
    sim.add_argument("--n-clients", type=int, default=100)
    sim.add_argument("--budgets", default="3.0",
                     help="comma list cycled over requests")
    sim.add_argument("--fused", action="store_true",
                     help="fused client eval (solo-optimized; batched "
                     "traffic defaults to the unfused body)")
    sim.add_argument("--exact", action="store_true",
                     help="exact mode: bit-equal to direct runs")
    sim.add_argument("--serial", action="store_true",
                     help="A/B baseline: direct sequential engine calls")
    sim.add_argument("--max-batch", type=int, default=16)
    sim.add_argument("--max-wait-ms", type=float, default=2.0)
    sim.add_argument("--threads", type=int, default=4)
    sim.add_argument("--verify", type=int, default=0, metavar="N",
                     help="cross-check N served results vs direct runs")
    sim.set_defaults(fn=_cmd_simulate)

    dec = sub.add_parser("decode", help="LM prefill+decode demo")
    dec.add_argument("--arch", default="qwen3-1.7b")
    dec.add_argument("--batch", type=int, default=4)
    dec.add_argument("--prompt-len", type=int, default=64)
    dec.add_argument("--gen", type=int, default=32)
    dec.add_argument("--layers", type=int, default=2)
    dec.add_argument("--d-model", type=int, default=256)
    dec.add_argument("--temperature", type=float, default=0.0)
    dec.set_defaults(fn=_cmd_decode)

    a = ap.parse_args()
    a.fn(a)


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    main()
