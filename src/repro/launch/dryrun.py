import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: jax builds the 16x16 (single-pod, 256 chips) and 2x16x16
(two-pod, 512 chips) meshes out of forced host devices, every step function
lowers with ShapeDtypeStruct inputs (zero allocation), GSPMD partitions it,
and the compiled artifact yields memory_analysis / cost_analysis /
collective schedule for EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--jobs-file cells.txt]

Each cell writes results/dryrun/<arch>__<shape>__<mesh>.json; re-runs skip
cells whose JSON already exists (incremental).
"""
import argparse
import json
import pathlib
import sys
import time
import traceback

import jax

from repro.configs import ARCH_NAMES, SHAPES, cells, get_config, get_recipe
from repro.launch.mesh import make_production_mesh
from repro.runtime import hlo_analysis as hlo
from repro.runtime import steps as steps_lib

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"


def n_params(cfg) -> tuple:
    """(total, active) parameter counts from the abstract tree."""
    from repro.models import transformer as tfm
    import numpy as np
    params, _ = tfm.init_params(cfg, abstract=True)
    total = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    active = total
    if cfg.n_experts:
        # active = total - (dormant experts): top_k of n_experts used/token
        per_expert = 3 * cfg.d_model * cfg.d_ff
        moe_layers = cfg.n_layers
        dormant = moe_layers * (cfg.n_experts - cfg.top_k) * per_expert
        active = total - dormant
    return total, active


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             overrides: dict | None = None) -> dict:
    cfg = get_config(arch)
    recipe = get_recipe(arch)
    if overrides:
        recipe.update({k: v for k, v in overrides.items()
                       if k in ("fsdp",)})
        overrides = dict(overrides)
        cfg_over = {k: v for k, v in overrides.items()
                    if k in ("attn_chunk", "moe_group", "attn_impl",
                             "remat_block", "attn_skip", "loss_chunk")}
        if cfg_over:
            cfg = cfg.replace(**cfg_over)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.devices.size

    t0 = time.time()
    if shape.mode == "train":
        bundle = steps_lib.make_train_step(
            cfg, mesh, seq_len=shape.seq_len,
            global_batch=shape.global_batch,
            fsdp=recipe["fsdp"], moment_dtype=recipe["moment_dtype"])
        args = (bundle.abstract_state, bundle.abstract_batch)
    elif shape.mode == "prefill":
        bundle = steps_lib.make_prefill_step(
            cfg, mesh, seq_len=shape.seq_len, global_batch=shape.global_batch,
            fsdp=recipe["fsdp"])
        args = (*bundle.abstract_state, bundle.abstract_batch)
    else:  # decode
        bundle = steps_lib.make_decode_step(
            cfg, mesh, seq_len=shape.seq_len, global_batch=shape.global_batch,
            fsdp=recipe["fsdp"])
        args = (*bundle.abstract_state, bundle.abstract_batch)

    with mesh:
        lowered = bundle.fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = hlo.memory_summary(compiled)
    pod_size = n_chips // mesh.shape.get("pod", 1) if mesh_kind == "multi" \
        else 0
    terms = hlo.roofline_terms(compiled, pod_size=pod_size)
    total_p, active_p = n_params(cfg)
    tokens = shape.global_batch * (shape.seq_len if shape.mode == "train"
                                   else (shape.seq_len if shape.mode ==
                                         "prefill" else 1))
    mflops = hlo.model_flops(active_p, tokens,
                             "train" if shape.mode == "train" else "serve")
    mflops_per_chip = mflops / n_chips
    useful = (mflops_per_chip / terms["hlo_flops"]
              if terms["hlo_flops"] else float("nan"))
    out = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "mode": shape.mode, "n_chips": n_chips,
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "fsdp": recipe["fsdp"],
        "moment_dtype": str(recipe["moment_dtype"].__name__),
        "params_total": total_p, "params_active": active_p,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": mem,
        "hbm_gb_per_chip": round(mem["per_device_bytes"] / 2**30, 3),
        "roofline": terms,
        "model_flops_per_chip": mflops_per_chip,
        "useful_flop_frac": useful,
        "overrides": overrides or {},
    }
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="", help="suffix for result files "
                    "(perf experiments)")
    ap.add_argument("--override", default="", help="k=v[,k=v] cfg overrides")
    args = ap.parse_args(argv)

    overrides = {}
    for kv in filter(None, args.override.split(",")):
        k, v = kv.split("=")
        overrides[k] = (v == "true") if v in ("true", "false") else (
            v if not v.lstrip("-").isdigit() else int(v))

    RESULTS.mkdir(parents=True, exist_ok=True)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        run, skip = cells(ARCH_NAMES)
        jobs = [(a, s, m) for (a, s) in run for m in meshes]
        for a, s, why in skip:
            print(f"SKIP {a} {s}: {why}")
    else:
        assert args.arch and args.shape
        jobs = [(args.arch, args.shape, m) for m in meshes]

    failures = 0
    for arch, shape, mesh_kind in jobs:
        tag = f"__{args.tag}" if args.tag else ""
        path = RESULTS / f"{arch}__{shape}__{mesh_kind}{tag}.json"
        if path.exists() and not args.force:
            print(f"CACHED {path.name}")
            continue
        try:
            res = run_cell(arch, shape, mesh_kind, overrides or None)
            path.write_text(json.dumps(res, indent=1))
            r = res["roofline"]
            print(f"OK {arch} {shape} {mesh_kind}: "
                  f"hbm={res['hbm_gb_per_chip']}GiB "
                  f"compute={r['compute_s']:.2e}s mem={r['memory_s']:.2e}s "
                  f"coll={r['collective_s']:.2e}s dom={r['dominant']} "
                  f"(compile {res['compile_s']}s)", flush=True)
        except Exception as e:  # noqa: BLE001 — record the failure, continue
            failures += 1
            print(f"FAIL {arch} {shape} {mesh_kind}: {e}", flush=True)
            traceback.print_exc()
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
