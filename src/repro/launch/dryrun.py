# The dry-run (and ONLY the dry-run) needs placeholder devices so the
# production mesh can be built on this CPU-only container: 512 for the
# real meshes, 8 for the --reduced grid.  These lines MUST run before any
# other import — jax locks the device count on first initialization, so
# the flag has to be chosen from argv before anything imports jax.
import os
import sys
_N_FORCED = "8" if "--reduced" in sys.argv else "512"
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                           + _N_FORCED + " "
                           + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
pair on the production meshes, and extract the roofline terms.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b \
        --shape train_4k --mesh pod
    PYTHONPATH=src python -m repro.launch.dryrun --all   # subprocess/pair

Each run writes experiments/dryrun/<arch>__<shape>__<mesh>[__tag].json with
memory_analysis, cost_analysis FLOPs/bytes, and per-collective byte counts
parsed from the partitioned HLO (per-device shard shapes).  Those JSONs are
the single source of truth for EXPERIMENTS.md §Dry-run and §Roofline.

``--reduced`` swaps the 512-device production mesh for a miniature
(pod=2, data=2, model=2) mesh of 8 forced host devices and shrinks every
architecture/input shape (``ArchConfig.reduced()``, capped batch/seq) —
the same reduction tests/test_distribution.py compiles.  ``--reduced
--all`` regenerates the committed ``experiments/dryrun`` artifact grid
(docs/sweeps.md documents this); the full 512-device sweep stays an
off-CI manual run.
"""

import argparse
import json
import re
import subprocess
import time

# the committed reduced-grid artifact set: one representative per model
# family x {train, decode} (the two modes with distinct sharding rules)
REDUCED_GRID = [
    ("qwen3-1.7b", "train_4k"), ("qwen3-1.7b", "decode_32k"),
    ("mamba2-370m", "train_4k"), ("mamba2-370m", "decode_32k"),
    ("mixtral-8x22b", "train_4k"), ("mixtral-8x22b", "decode_32k"),
    ("deepseek-v2-236b", "train_4k"), ("deepseek-v2-236b", "decode_32k"),
]

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(pred|[suf]\d+|bf16|c64|c128)\[([\d,]*)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op (per-device shards).

    HLO lines look like:  %ag = bf16[128,5760]{1,0} all-gather(...)
    For tuple results every element shape is counted.
    """
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"%?[\w.\-]+ = (.*)$", ls)
        if not m:
            continue
        rhs = m.group(1)
        for cname in _COLLECTIVES:
            # match the op name right after the result shape
            opm = re.match(r"((?:\()?[\w\[\]{},\s/#*]*?(?:\))?)\s*" + cname
                           + r"(?:-start|-done)?\(", rhs)
            if opm:
                # -done ops repeat the shape of -start; count starts only
                if cname + "-done(" in rhs:
                    break
                out[cname] += _shape_bytes(opm.group(1))
                counts[cname] += 1
                break
    out_total = sum(out.values())
    return {"bytes": out, "counts": counts, "total_bytes": out_total}


def run_one(arch: str, shape: str, mesh_name: str, *, fsdp=None, accum=None,
            expert_parallel=None, ce_chunk=None, accum_dtype="float32",
            out_dir="experiments/dryrun", tag="", reduced=False):
    import jax
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import build_dryrun

    t0 = time.time()
    if reduced:
        mesh_name = "reduced8"
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=(mesh_name == "multipod"))
    fn, args, in_specs, out_specs, meta = build_dryrun(
        arch, shape, mesh, fsdp=fsdp, accum=accum,
        expert_parallel=expert_parallel, ce_chunk=ce_chunk,
        accum_dtype=accum_dtype, reduced=reduced)
    meta["ce_chunk"] = ce_chunk
    meta["mesh"] = mesh_name
    meta["devices"] = int(mesh.devices.size)
    meta["reduced"] = bool(reduced)

    from repro.launch.mesh import as_shardings
    jax.set_mesh(mesh)
    in_specs = as_shardings(mesh, in_specs)
    out_specs = as_shardings(mesh, out_specs)
    # serving donates the KV/SSM caches (argument 1): the updated cache
    # aliases the input buffer instead of double-buffering — on v5e this
    # is the difference between fitting and not for the 32k MHA caches.
    donate = (1,) if meta["mode"] in ("decode", "prefill") else ()
    jitted = jax.jit(fn, in_shardings=in_specs, out_shardings=out_specs,
                     donate_argnums=donate)
    lowered = jitted.lower(*args)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    rec = dict(meta)
    rec["ok"] = True
    rec["t_lower_s"] = round(t_lower, 2)
    rec["t_compile_s"] = round(t_compile, 2)

    try:
        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "generated_code_bytes": int(ma.generated_code_size_in_bytes),
        }
    except Exception as e:  # backend-dependent
        rec["memory"] = {"error": str(e)}

    try:
        ca = compiled.cost_analysis()
        rec["cost"] = {k: float(v) for k, v in ca.items()
                       if isinstance(v, (int, float)) and
                       ("flops" in k or "bytes" in k or "utilization" not in k)}
        rec["flops"] = float(ca.get("flops", 0.0))
        rec["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
    except Exception as e:
        rec["cost"] = {"error": str(e)}

    hlo = compiled.as_text()
    rec["collectives"] = collective_bytes(hlo)
    rec["hlo_chars"] = len(hlo)
    # trip-count-weighted analysis (XLA counts loop bodies once; see
    # repro.launch.hloparse) — the roofline reads these fields.
    from repro.launch.hloparse import analyze_hlo
    rec["weighted"] = analyze_hlo(hlo)

    os.makedirs(out_dir, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    path = os.path.join(out_dir, f"{arch}__{shape}__{mesh_name}{suffix}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps({k: rec[k] for k in
                      ("arch", "shape", "mesh", "ok", "t_compile_s")}))
    print("memory:", rec["memory"])
    print("flops:", rec.get("flops"), "bytes:", rec.get("bytes_accessed"))
    print("collectives:", rec["collectives"]["total_bytes"],
          rec["collectives"]["counts"])
    return rec


def run_all(meshes, out_dir, timeout=1800, only_missing=False,
            reduced=False):
    from repro.launch.specs import dryrun_pairs
    if reduced:
        meshes, pairs = ["reduced8"], REDUCED_GRID
    else:
        pairs = dryrun_pairs()
    results = []
    for mesh_name in meshes:
        for arch, shape in pairs:
            path = os.path.join(out_dir, f"{arch}__{shape}__{mesh_name}.json")
            if only_missing and os.path.exists(path):
                ok = json.load(open(path)).get("ok", False)
                if ok:
                    results.append((arch, shape, mesh_name, "cached"))
                    continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--out-dir", out_dir]
            cmd += ["--reduced"] if reduced else ["--mesh", mesh_name]
            t0 = time.time()
            try:
                p = subprocess.run(cmd, capture_output=True, text=True,
                                   timeout=timeout)
                ok = "ok" if p.returncode == 0 else "FAIL"
                if p.returncode != 0:
                    err_path = path.replace(".json", ".err")
                    with open(err_path, "w") as f:
                        f.write(p.stdout[-4000:] + "\n" + p.stderr[-8000:])
            except subprocess.TimeoutExpired:
                ok = "TIMEOUT"
            results.append((arch, shape, mesh_name, ok))
            print(f"[{len(results)}] {arch} {shape} {mesh_name}: {ok} "
                  f"({time.time()-t0:.0f}s)", flush=True)
    bad = [r for r in results if r[3] not in ("ok", "cached")]
    print(f"\n{len(results)-len(bad)}/{len(results)} ok; failures: {bad}")
    return 1 if bad else 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--reduced", action="store_true",
                    help="8-host-device (2,2,2) mesh + reduced arch/shapes "
                    "(the committed artifact grid; see docs/sweeps.md)")
    ap.add_argument("--only-missing", action="store_true")
    ap.add_argument("--meshes", default="pod,multipod")
    ap.add_argument("--fsdp", type=int, default=None)
    ap.add_argument("--accum", type=int, default=None)
    ap.add_argument("--expert-parallel", type=int, default=None)
    ap.add_argument("--ce-chunk", type=int, default=None)
    ap.add_argument("--accum-dtype", default="float32")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out-dir", default="experiments/dryrun")
    ap.add_argument("--timeout", type=int, default=1800)
    args = ap.parse_args()

    if args.all:
        sys.exit(run_all(args.meshes.split(","), args.out_dir,
                         timeout=args.timeout,
                         only_missing=args.only_missing,
                         reduced=args.reduced))
    fsdp = None if args.fsdp is None else bool(args.fsdp)
    ep = None if args.expert_parallel is None else bool(args.expert_parallel)
    run_one(args.arch, args.shape, args.mesh, fsdp=fsdp, accum=args.accum,
            expert_parallel=ep, ce_chunk=args.ce_chunk,
            accum_dtype=args.accum_dtype,
            out_dir=args.out_dir, tag=args.tag, reduced=args.reduced)


if __name__ == "__main__":
    main()
