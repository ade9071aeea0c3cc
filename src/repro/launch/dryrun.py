import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# NOTE: the two lines above MUST be the first statements in this module —
# before any other import, including jax — because jax locks the device count
# on first init.  (A __future__ import is therefore impossible here; this
# module avoids needing one.)

_DOC = """Multi-pod dry-run (deliverable e).

For every (architecture × input shape × mesh) cell:
    with mesh:
        lowered  = jax.jit(step, donate_argnums=…).lower(*abstract_inputs)
        compiled = lowered.compile()
        print(compiled.memory_analysis())   # proves it fits
        print(compiled.cost_analysis())     # FLOPs/bytes for §Roofline
plus trip-corrected collective parsing and the analytic cost model, appended
as one JSON record per cell to ``--out`` (default results/dryrun.jsonl).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax

from repro.configs.base import ALL_ARCH_IDS, SHAPES
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_step_bundle
from repro.models.registry import get_arch
from repro.roofline.analysis import analyze_compiled, roofline_terms
from repro.roofline.analytic import analytic_cost
from repro.roofline.hw import TPU_V5E
from repro.sharding.mesh import make_plan
from repro.utils.logging import get_logger

log = get_logger("dryrun")


def run_cell(
    arch_id: str,
    shape_name: str,
    multi_pod: bool,
    plan_overrides: dict | None = None,
    verbose: bool = True,
) -> dict:
    shape = SHAPES[shape_name]
    arch = get_arch(arch_id)
    mesh_name = "multi(2,16,16)" if multi_pod else "single(16,16)"
    rec: dict = {
        "arch": arch_id,
        "shape": shape_name,
        "mesh": mesh_name,
        "kind": shape.kind,
    }
    ok, reason = arch.supports(shape)
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        n_chips = int(len(mesh.devices.reshape(-1)))
        plan = make_plan(arch.cfg, mesh, shape.global_batch, **(plan_overrides or {}))
        bundle = build_step_bundle(arch, shape, plan)
        with mesh:
            lowered = jax.jit(
                bundle.fn, donate_argnums=bundle.donate_argnums
            ).lower(*bundle.abstract_args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            ma = compiled.memory_analysis()
            if verbose:
                print(f"[{arch_id} × {shape_name} × {mesh_name}] {bundle.name}")
                print("  memory_analysis:", ma)
                print("  cost_analysis: flops=%.3e bytes=%.3e" % (
                    (compiled.cost_analysis() or {}).get("flops", 0.0),
                    (compiled.cost_analysis() or {}).get("bytes accessed", 0.0),
                ))
            stats = analyze_compiled(compiled)
        cache_bpe = 1.03 if plan.cache_quant_int8 else 2.0
        cost = analytic_cost(arch.cfg, shape, cache_bytes_per_elem=cache_bpe)
        terms = roofline_terms(
            model_flops=cost.model_flops,
            exec_flops=cost.hlo_flops_est,
            hbm_bytes=cost.hbm_bytes,
            collective_bytes_per_dev=stats.collective_bytes_per_dev,
            n_chips=n_chips,
            hw=TPU_V5E,  # the production mesh models v5e pods, not this host
        )
        rec.update(
            status="ok",
            step_fn=bundle.name,
            n_chips=n_chips,
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            memory={
                "argument_bytes_per_dev": stats.argument_bytes,
                "output_bytes_per_dev": stats.output_bytes,
                "temp_bytes_per_dev": stats.temp_bytes,
                "alias_bytes_per_dev": stats.alias_bytes,
                "peak_bytes_per_dev_est": stats.peak_bytes_est,
            },
            hlo_cost={
                "flops_per_dev_raw": stats.hlo_flops_per_dev,
                "bytes_per_dev_raw": stats.hlo_bytes_per_dev,
            },
            collectives={
                "counts": stats.collective_counts,
                "wire_bytes_per_dev": stats.collective_bytes_per_dev,
                "by_kind": stats.collective_bytes_by_kind,
            },
            analytic={
                "model_flops": cost.model_flops,
                "exec_flops_est": cost.hlo_flops_est,
                "hbm_bytes": cost.hbm_bytes,
                "n_active_params": cost.n_active,
                "n_total_params": cost.n_total,
            },
            roofline=terms.as_dict(),
        )
    except Exception as e:  # a failing cell is a bug — record it loudly
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
        log.error("FAILED %s × %s × %s: %s", arch_id, shape_name, mesh_name, e)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ALL_ARCH_IDS)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true", help="every live cell")
    ap.add_argument("--out", default="results/dryrun.jsonl")
    ap.add_argument("--seq-shard-cache", action="store_true",
                    help="flash-decode KV-seq sharding (§Perf variant)")
    ap.add_argument("--cache-int8", action="store_true",
                    help="int8 KV cache — SONIC C2 on the cache (§Perf)")
    ap.add_argument("--serve-stationary", action="store_true",
                    help="TP-only (no-FSDP) serving weights (§Perf)")
    args = ap.parse_args()

    archs = ALL_ARCH_IDS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    overrides = {}
    if args.seq_shard_cache:
        overrides["seq_shard_cache"] = True
    if args.cache_int8:
        overrides["cache_quant_int8"] = True
    if args.serve_stationary:
        overrides["serve_stationary"] = True
    overrides = overrides or None

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    n_ok = n_skip = n_err = 0
    with open(args.out, "a") as f:
        for aid in archs:
            for sname in shapes:
                for mp in meshes:
                    rec = run_cell(aid, sname, mp, overrides)
                    f.write(json.dumps(rec) + "\n")
                    f.flush()
                    n_ok += rec["status"] == "ok"
                    n_skip += rec["status"] == "skipped"
                    n_err += rec["status"] == "error"
                    tag = {"ok": "OK ", "skipped": "SKIP", "error": "ERR "}[rec["status"]]
                    dom = rec.get("roofline", {}).get("dominant", "-")
                    log.info("%s %s × %s × %s (dominant=%s)", tag, aid, sname,
                             rec["mesh"], dom)
    log.info("dry-run complete: %d ok, %d skipped, %d errors", n_ok, n_skip, n_err)
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
