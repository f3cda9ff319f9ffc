"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Per cell this produces (cached as JSON under experiments/dryrun/):

* proof-of-compile on the production mesh — 16×16 (pod) and 2×16×16
  (multi-pod);
* `memory_analysis()` (bytes per device) and `cost_analysis()`;
* the collective schedule (op kinds / counts / ring wire bytes);
* compositional exact costs (repro.launch.components) and the three
  roofline terms (repro.launch.roofline).

Usage:
    python -m repro.launch.dryrun --arch grok-1-314b --shape train_4k
    python -m repro.launch.dryrun --arch all --shape all [--multi-pod]
    python -m repro.launch.dryrun ... --skip-costs   (compile proof only)
"""
import argparse
import json
import os
import sys
import time
import traceback

import jax

from repro.configs import ARCH_IDS, ALIASES, SHAPES, RunConfig, get_config
from repro.launch import mesh as mesh_lib
from repro.launch import roofline as rl
from repro.launch.components import compute_cell_costs
from repro.launch.specs import build_cell, default_run_config

DEFAULT_OUT = "experiments/dryrun"


def run_cell(arch: str, shape_name: str, multi_pod: bool, run_cfg=None,
             skip_costs: bool = False, tag: str = "") -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    n_dev = 512 if multi_pod else 256
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    run_cfg = run_cfg or default_run_config(shape.kind)
    t0 = time.time()
    cell = build_cell(arch, shape_name, mesh, run_cfg)
    lowered = jax.jit(
        cell.fn, out_shardings=cell.out_shardings, donate_argnums=cell.donate
    ).lower(*cell.args)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower
    mem = rl.memory_analysis_dict(compiled)
    print(compiled.memory_analysis())
    ca = compiled.cost_analysis()
    print({k: v for k, v in ca.items() if k in ("flops", "bytes accessed")})
    colls = rl.collective_wire_bytes(compiled.as_text())

    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "n_devices": n_dev,
        "status": "ok",
        "lower_s": t_lower,
        "compile_s": t_compile,
        "memory": mem,
        "full_step_cost_analysis": {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            "note": "scan bodies counted once; see components for exact costs",
        },
        "full_step_collectives": colls,
        "run_config": {
            "attn_impl": run_cfg.attn_impl, "q_chunk": run_cfg.q_chunk,
            "kv_chunk": run_cfg.kv_chunk, "remat": run_cfg.remat,
            "moe_impl": run_cfg.moe_impl, "ce_chunk": run_cfg.ce_chunk,
            "skip_masked_blocks": run_cfg.skip_masked_blocks,
        },
        "tag": tag,
    }

    if not skip_costs:
        costs = compute_cell_costs(cfg, shape, run_cfg, mesh)
        per_dev = costs["per_device"]
        report = rl.RooflineReport(
            arch=arch, shape=shape_name, mesh=mesh_name, cost=per_dev,
            model_flops_global=rl.model_flops(cfg, shape), n_devices=n_dev,
            memory=mem, collectives=colls, components=costs["components"],
        )
        result["roofline"] = report.to_dict()
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--skip-costs", action="store_true")
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--tag", default="baseline")
    # hillclimb overrides
    ap.add_argument("--attn-impl", default=None)
    ap.add_argument("--q-chunk", type=int, default=None)
    ap.add_argument("--kv-chunk", type=int, default=None)
    ap.add_argument("--skip-masked-blocks", action="store_true")
    ap.add_argument("--moe-impl", default=None)
    ap.add_argument("--ce-chunk", type=int, default=None)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--ce-impl", default=None)
    ap.add_argument("--decode-seq-shard", action="store_true")
    ap.add_argument("--constrain-activations", action="store_true")
    ap.add_argument("--accum", type=int, default=None)
    ap.add_argument("--bf16-params", action="store_true")
    args = ap.parse_args(argv)

    archs = ARCH_IDS if args.arch == "all" else [ALIASES.get(args.arch, args.arch)]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    os.makedirs(args.out, exist_ok=True)

    failures = 0
    for arch in archs:
        cfg = get_config(arch)
        for shape_name in shapes:
            if shape_name == "long_500k" and not cfg.supports_long_context:
                print(f"SKIP {arch} long_500k (full attention; DESIGN.md §4)")
                continue
            for mp in meshes:
                from dataclasses import replace as _r

                run_cfg = default_run_config(SHAPES[shape_name].kind)
                for field in ("attn_impl", "moe_impl", "remat", "ce_impl"):
                    v = getattr(args, field)
                    if v is not None:
                        run_cfg = _r(run_cfg, **{field: v})
                for field in ("q_chunk", "kv_chunk", "ce_chunk"):
                    v = getattr(args, field)
                    if v is not None:
                        run_cfg = _r(run_cfg, **{field: v})
                if args.skip_masked_blocks:
                    run_cfg = _r(run_cfg, skip_masked_blocks=True)
                if args.decode_seq_shard:
                    run_cfg = _r(run_cfg, decode_seq_shard=True)
                if args.constrain_activations:
                    run_cfg = _r(run_cfg, constrain_activations=True)
                if args.accum is not None:
                    run_cfg = _r(run_cfg, accum_steps=args.accum)
                if args.bf16_params:
                    run_cfg = _r(run_cfg, bf16_params=True)
                mesh_name = "pod2x16x16" if mp else "pod16x16"
                out_path = os.path.join(
                    args.out, f"{arch}__{shape_name}__{mesh_name}__{args.tag}.json"
                )
                label = f"{arch} × {shape_name} × {mesh_name}"
                print(f"=== {label} ===", flush=True)
                try:
                    result = run_cell(
                        arch, shape_name, mp, run_cfg,
                        skip_costs=args.skip_costs, tag=args.tag,
                    )
                except Exception as e:  # a failing cell is a bug — record it
                    traceback.print_exc()
                    result = {
                        "arch": arch, "shape": shape_name, "mesh": mesh_name,
                        "status": "error", "error": f"{type(e).__name__}: {e}",
                        "tag": args.tag,
                    }
                    failures += 1
                with open(out_path, "w") as f:
                    json.dump(result, f, indent=1)
                print(f"-> {out_path} [{result['status']}]", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    # before jax first initialises a backend (it locks the device count):
    # only the dry-run process forces 512 host devices
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    sys.exit(main())
