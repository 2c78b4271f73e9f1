"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this script:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. constructs ShapeDtypeStruct stand-ins for every input (no allocation),
  3. jits the step with explicit in_shardings (weights/optimizer state by the
     name-based TP rules, batch over DP axes, caches by the generic rule),
  4. ``.lower().compile()`` — a sharding mismatch, compile-OOM, or
     unsupported collective here is a bug in the framework,
  5. records ``memory_analysis()`` / ``cost_analysis()`` and the collective
     operand bytes parsed from the optimized HLO into a JSON artifact that
     ``benchmarks/roofline.py`` consumes.

Usage:
    python -m repro.launch.dryrun --arch gemma-2b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all --out results/dryrun   # full sweep
"""
from __future__ import annotations

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"  # MUST precede any jax import

import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.distributed import sharding as shd
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.models import lm
from repro.optim import PantherConfig
from repro.optim.schedules import constant
from repro.serve.step import make_decode_step, make_prefill
from repro.train.step import batch_specs, make_train_step, train_state_init, train_state_specs

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute")


def parse_collective_bytes(hlo_text: str) -> dict:
    """Sum result-operand bytes of every collective op in optimized HLO."""
    out = {c: 0 for c in _COLLECTIVES}
    counts = {c: 0 for c in _COLLECTIVES}
    # lines like:  %name = bf16[16,128]{1,0} all-reduce(...)  or tuple results
    pat = re.compile(r"=\s*(\([^)]*\)|\S+)\s+(" + "|".join(_COLLECTIVES) + r")(?:-start|-done)?\(")
    typ = re.compile(r"(\w+)\[([\d,]*)\]")
    for line in hlo_text.splitlines():
        mm = pat.search(line)
        if not mm:
            continue
        types, op = mm.group(1), mm.group(2)
        total = 0
        for dt, dims in typ.findall(types):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            if dims:
                for d in dims.split(","):
                    n *= int(d)
            total += n * _DTYPE_BYTES[dt]
        out[op] += total
        counts[op] += 1
    return {"bytes": out, "counts": counts, "total_bytes": sum(out.values())}


def _named(mesh, tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree, is_leaf=lambda x: isinstance(x, P))


def _serve_params(cfg):
    """Abstract bf16 serving params (dequantized crossbar state)."""
    shapes = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16 if l.ndim >= 2 else l.dtype), shapes
    )


MICROBATCH_OVERRIDE = None


def choose_microbatches(cfg, mesh, B: int, S: int) -> int:
    """Pick gradient-accumulation depth so per-microbatch scan-carry
    activations stay ~<=3 GiB/device (B_dev * S * d * 2B * L / G)."""
    if MICROBATCH_OVERRIDE is not None:
        return MICROBATCH_OVERRIDE
    dp = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names and B % (dp * mesh.shape[a]) == 0:
            dp *= mesh.shape[a]
    b_dev = max(B // dp, 1)
    carry_bytes = b_dev * S * cfg.d_model * 2 * max(cfg.n_layers, 1)
    target = 3 * 2**30
    g = 1
    while carry_bytes / g > target and g < b_dev:
        g *= 2
    return g


KV_DTYPE = jnp.bfloat16  # set to jnp.int8 via --kv-dtype for the §Perf cell
TRAIN_REMAT = "full"  # --remat dots: save matmuls (§Perf compute-term lever)
GRAD_DTYPE = jnp.float32  # --grad-dtype bf16: halve grad RS bytes (§Perf)


def input_specs(cfg, shape_name: str, microbatches: int = 1):
    """ShapeDtypeStruct stand-ins for one cell's inputs."""
    shape = configs.SHAPES[shape_name]
    B, S = shape["global_batch"], shape["seq_len"]
    kind = shape["kind"]
    if cfg.input_mode == "tokens":
        tok = lambda b, s: jax.ShapeDtypeStruct((b, s), jnp.int32)
    else:
        tok = lambda b, s: jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16)
    if kind == "train":
        if microbatches > 1:
            g, b = microbatches, B // microbatches
            mb = lambda t: jax.ShapeDtypeStruct((g,) + t.shape, t.dtype)
            return {"inputs": mb(tok(b, S)), "labels": mb(jax.ShapeDtypeStruct((b, S), jnp.int32))}
        return {"inputs": tok(B, S), "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    if kind == "prefill":
        return {"inputs": tok(B, S)}
    # decode: one new token against a cache of S
    if cfg.input_mode == "tokens":
        token = jax.ShapeDtypeStruct((B,), jnp.int32)
    else:
        token = jax.ShapeDtypeStruct((B, 1, cfg.d_model), jnp.bfloat16)
    return {
        "token": token,
        "caches": lm.cache_specs(cfg, B, S, KV_DTYPE, layout="list"),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }


def build_cell(arch: str, shape_name: str, mesh):
    """Returns (jitted_fn, lower_args) for one cell."""
    cfg = configs.get(arch)
    shape = configs.SHAPES[shape_name]
    B, S = shape["global_batch"], shape["seq_len"]
    kind = shape["kind"]

    if kind == "train":
        g = choose_microbatches(cfg, mesh, B, S)
        build_cell.last_knobs = {"microbatches": g, "remat": TRAIN_REMAT,
                                 "grad_dtype": str(GRAD_DTYPE.__name__ if hasattr(GRAD_DTYPE, '__name__') else GRAD_DTYPE)}
        ins = input_specs(cfg, shape_name, microbatches=g)
        opt_cfg = PantherConfig(stochastic_round=True, compute_dtype=jnp.bfloat16)
        step = make_train_step(
            cfg, opt_cfg, constant(1e-3), mesh=mesh, global_batch=B, microbatches=g, fsdp=True,
            remat=TRAIN_REMAT, grad_dtype=GRAD_DTYPE,
        )
        state_shapes = jax.eval_shape(lambda: train_state_init(cfg, opt_cfg, jax.random.PRNGKey(0)))
        sspecs = _named(mesh, train_state_specs(cfg, opt_cfg, mesh=mesh, fsdp=True))
        bspecs = _named(mesh, batch_specs(cfg, mesh, B, microbatches=g))
        jitted = jax.jit(step, in_shardings=(sspecs, bspecs), donate_argnums=0)
        return jitted, (state_shapes, ins)
    ins = input_specs(cfg, shape_name)

    params_shapes = _serve_params(cfg)
    pspecs = _named(mesh, shd.param_specs(params_shapes, mesh=mesh))
    if kind == "prefill":
        fn = make_prefill(cfg, mesh=mesh, global_batch=B, max_seq=S)
        ispec = NamedSharding(mesh, shd.data_spec(mesh, B, 2 if cfg.input_mode == "tokens" else 3))
        # pin output caches (stacked layout) or XLA materializes them
        # under-sharded — the multi-TB KV of 32k prefill must stay sharded
        cache_shapes = lm.cache_specs(cfg, B, S, jnp.bfloat16, layout="stacked")
        cspecs = _named(mesh, shd.cache_specs(mesh, cache_shapes, B))
        lspec = NamedSharding(mesh, shd.data_spec(mesh, B, 2))
        jitted = jax.jit(fn, in_shardings=(pspecs, ispec), out_shardings=(lspec, cspecs))
        return jitted, (params_shapes, ins["inputs"])

    # decode
    fn = make_decode_step(cfg, mesh=mesh, global_batch=B)
    cspecs = _named(mesh, shd.cache_specs(mesh, ins["caches"], B))
    tspec = NamedSharding(mesh, shd.data_spec(mesh, B, 1 if cfg.input_mode == "tokens" else 3))
    lspec = NamedSharding(mesh, shd.data_spec(mesh, B, 2))
    # pinning out caches to the in specs makes the donation alias bind
    # (cache update stays in place — the serving memory contract)
    jitted = jax.jit(
        fn,
        in_shardings=(pspecs, tspec, cspecs, NamedSharding(mesh, P())),
        out_shardings=(NamedSharding(mesh, shd.data_spec(mesh, B, 1)), lspec, cspecs),
        donate_argnums=2,
    )
    return jitted, (params_shapes, ins["token"], ins["caches"], ins["pos"])


def run_cell(arch: str, shape_name: str, mesh_kind: str, tp: int | None = None) -> dict:
    if tp is not None and mesh_kind == "single":
        mesh = make_mesh((256 // tp, tp), ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind, "n_devices": mesh.size,
           "tp": mesh.shape["model"], "kv_dtype": str(KV_DTYPE.__name__)}
    build_cell.last_knobs = {}
    t0 = time.time()
    with jax.set_mesh(mesh):
        jitted, args = build_cell(arch, shape_name, mesh)
        lowered = jitted.lower(*args)
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

        ma = compiled.memory_analysis()
        if ma is not None:
            rec["memory"] = {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
                "peak_per_device_bytes": int(
                    ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes
                    - ma.alias_size_in_bytes
                ),
            }
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        if ca:
            rec["cost"] = {
                "flops": float(ca.get("flops", -1)),
                "transcendentals": float(ca.get("transcendentals", -1)),
                "bytes_accessed": float(ca.get("bytes accessed", -1)),
            }
        rec["collectives"] = parse_collective_bytes(compiled.as_text())
    rec.update(getattr(build_cell, "last_knobs", {}))
    rec["status"] = "ok"
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(configs.SHAPES) + [None])
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true", help="run every supported cell")
    ap.add_argument("--out", default=None, help="output dir for JSON artifacts")
    ap.add_argument("--kv-dtype", default="bf16", choices=["bf16", "int8"],
                    help="decode KV-cache dtype (int8 = quantized cache, §Perf)")
    ap.add_argument("--remat", default="full", choices=["full", "dots"],
                    help="train remat policy (§Perf compute-term lever)")
    ap.add_argument("--grad-dtype", default="f32", choices=["f32", "bf16"],
                    help="grad accumulation/reduction dtype (§Perf collective lever)")
    ap.add_argument("--tp", type=int, default=None,
                    help="override model-axis width on the single-pod mesh (§Perf)")
    ap.add_argument("--mb", type=int, default=None,
                    help="override gradient-accumulation microbatch count (§Perf)")
    args = ap.parse_args()
    if args.mb is not None:
        global MICROBATCH_OVERRIDE
        MICROBATCH_OVERRIDE = args.mb
    global KV_DTYPE, TRAIN_REMAT, GRAD_DTYPE
    if args.kv_dtype == "int8":
        KV_DTYPE = jnp.int8
    TRAIN_REMAT = args.remat
    if args.grad_dtype == "bf16":
        GRAD_DTYPE = jnp.bfloat16

    cells = []
    archs = list(configs.ALIASES) if (args.all or args.arch is None) else [args.arch]
    for arch in archs:
        shapes = configs.shape_cells(arch) if (args.all or args.shape is None) else [args.shape]
        meshes = ["single", "multi"] if args.mesh == "both" or args.all else [args.mesh]
        for s in shapes:
            for m in meshes:
                cells.append((arch, s, m))

    results = []
    for arch, s, m in cells:
        name = f"{arch}|{s}|{m}"
        try:
            rec = run_cell(arch, s, m, tp=args.tp)
            print(f"[ok] {name}: compile={rec['compile_s']}s "
                  f"peak/dev={rec.get('memory', {}).get('peak_per_device_bytes', -1)/2**30:.2f}GiB "
                  f"flops={rec.get('cost', {}).get('flops', -1):.3g} "
                  f"coll={rec['collectives']['total_bytes']/2**20:.1f}MiB", flush=True)
        except Exception as e:  # noqa: BLE001 — record and continue the sweep
            rec = {"arch": arch, "shape": s, "mesh": m, "status": "fail",
                   "error": f"{type(e).__name__}: {e}", "trace": traceback.format_exc()[-2000:]}
            print(f"[FAIL] {name}: {type(e).__name__}: {str(e)[:200]}", flush=True)
        results.append(rec)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            fname = f"{arch.replace('.', 'p').replace('-', '_')}__{s}__{m}.json"
            with open(os.path.join(args.out, fname), "w") as f:
                json.dump(rec, f, indent=1)

    ok = sum(1 for r in results if r.get("status") == "ok")
    print(f"\n{ok}/{len(results)} cells compiled successfully")
    if args.out:
        with open(os.path.join(args.out, "summary.json"), "w") as f:
            json.dump(results, f, indent=1)
    return 0 if ok == len(results) else 1


if __name__ == "__main__":
    raise SystemExit(main())
