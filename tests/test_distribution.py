"""Distribution tests: sharding rules (pure), and a reduced-mesh dry-run in
a subprocess with 8 forced host devices (the miniature of deliverable e)."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax
import jax.numpy as jnp

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def test_param_specs_divisibility_guard():
    from repro.launch.sharding import param_specs
    from jax.sharding import PartitionSpec as P
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    # fake mesh with axis sizes 1 never rejects; use a shape-only check via
    # a synthetic mesh object is not possible -> use the real guard through
    # shapes divisible/indivisible by 1 (trivially divisible).  The real
    # divisibility behaviour is covered in the subprocess test below.
    shapes = {"wq": jax.ShapeDtypeStruct((8, 16), jnp.float32),
              "ln1": jax.ShapeDtypeStruct((8,), jnp.float32)}
    specs = param_specs(shapes, mesh)
    assert specs["wq"] == P(None, "model")
    assert specs["ln1"] == P()


def test_cache_spec_names():
    from repro.launch.sharding import cache_specs
    from jax.sharding import PartitionSpec as P
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    shapes = {
        "k": jax.ShapeDtypeStruct((4, 2, 64, 1, 8), jnp.float32),
        "v": jax.ShapeDtypeStruct((4, 2, 64, 1, 8), jnp.float32),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }
    specs = cache_specs(shapes, mesh)
    assert specs["pos"] == P()
    # batch dim (=2, divisible by 1) sharded over data, kv heads over model
    assert specs["k"][1] in ("data", ("data",))
    assert specs["k"][3] == "model"


_SUBPROCESS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
import numpy as np
import dataclasses

from repro.launch import sharding as sh
from repro.models import get_config, model
from repro.optim import AdamWConfig, make_train_step, init_train_state
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import as_shardings

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
jax.set_mesh(mesh)

cfg = get_config("qwen3-1.7b").reduced(n_layers=2, vocab_size=2048,
                                       d_model=256, n_heads=4, n_kv_heads=2)
key = jax.random.PRNGKey(0)
p_shapes = jax.eval_shape(lambda k: model.init_params(cfg, k, jnp.bfloat16), key)
pspecs = sh.param_specs(p_shapes, mesh)
opt_cfg = AdamWConfig()

class B:
    pass

def loss(params, b):
    return model.loss_fn(cfg, params, b)

from typing import NamedTuple
class Batch(NamedTuple):
    tokens: object
    targets: object
    mask: object

step = make_train_step(lambda p, b: model.loss_fn(cfg, p, Batch(*b)),
                       opt_cfg, accum_steps=2,
                       microbatch_spec=P(("pod", "data")))
state_shapes = jax.eval_shape(
    lambda k: init_train_state(model.init_params(cfg, k, jnp.bfloat16),
                               opt_cfg), key)
sspecs = sh.train_state_specs(state_shapes, pspecs)
batch = tuple(jax.ShapeDtypeStruct((16, 64), d)
              for d in (jnp.int32, jnp.int32, jnp.float32))
bspecs = sh.batch_specs(batch, mesh)
lowered = jax.jit(step, in_shardings=as_shardings(mesh, (sspecs, bspecs)),
                  out_shardings=as_shardings(mesh, (sspecs, None))
                  ).lower(state_shapes, batch)
compiled = lowered.compile()

ma = compiled.memory_analysis()
ca = compiled.cost_analysis()
print(json.dumps({
    "ok": True,
    "devices": jax.device_count(),
    "temp": int(ma.temp_size_in_bytes),
    "flops": float(ca.get("flops", 0)),
}))
"""


def test_multipod_reduced_dryrun_subprocess():
    """Lower + compile a reduced train step on a (pod, data, model) mesh of
    8 forced host devices — validates mesh/specs end to end without the
    512-device production compile."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    # forced host devices only exist on the CPU platform; pinning it also
    # skips the (slow, sandbox-hostile) accelerator backend probe.
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "-c", _SUBPROCESS_SCRIPT],
                       capture_output=True, text=True, env=env, timeout=420)
    assert p.returncode == 0, p.stderr[-3000:]
    rec = json.loads(p.stdout.strip().splitlines()[-1])
    assert rec["ok"] and rec["devices"] == 8
    assert rec["flops"] > 0


def test_production_dryrun_artifacts_if_present():
    """When the full sweep has run (experiments/dryrun), every pair must
    have succeeded on both meshes."""
    d = os.path.join(os.path.dirname(__file__), "..", "experiments", "dryrun")
    if not os.path.isdir(d) or not os.listdir(d):
        pytest.skip("production dry-run artifacts not generated yet")
    recs = []
    for f in os.listdir(d):
        if f.endswith(".json"):
            recs.append(json.load(open(os.path.join(d, f))))
    assert recs
    for r in recs:
        assert r.get("ok"), f"{r.get('arch')}/{r.get('shape')}/{r.get('mesh')}"
