"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

``main`` asks XLA for 512 virtual host devices before the first device call
(jax locks the device count when the backend starts); importing this module
changes no environment.

For each cell we build abstract params/optimizer/batch (ShapeDtypeStructs,
no allocation), jit the step with explicit in/out shardings on the
production mesh, ``.lower().compile()``, and record:

* ``memory_analysis``  — proves the cell fits 16 GB/chip,
* ``cost_analysis``    — FLOPs / bytes for the roofline terms,
* parsed collective bytes (see ``repro.roofline.analysis``).

Results accumulate in ``results/dryrun/<cell>.json``; benchmarks and
EXPERIMENTS.md read from there.

Usage:
    python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
    python -m repro.launch.dryrun --all --multi-pod
    python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import json
import os
import time
import traceback

from ..configs import ALL_SHAPES, ARCHS, SHAPES, shape_applicable
from ..models import params as PM
from ..roofline.analysis import RooflineReport, model_flops
from ..roofline.hlo_walk import analyze as hlo_analyze
from .compile_cache import enable_compile_cache
from .mesh import make_production_mesh
from .sharded_step import build_sharded_step

HBM_PER_CHIP = 16e9          # TPU v5e

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "results", "dryrun")


def _compile_cell(cfg, shape, mesh):
    step = build_sharded_step(cfg, shape, mesh)
    t0 = time.time()
    lowered = step.jitted.lower(*step.args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    return step.layout, compiled, t_lower, time.time() - t0


def run_cell(
    arch: str, shape_name: str, *,
    multi_pod: bool = False, overrides: dict = None, save: bool = True,
):
    from dataclasses import replace

    cfg = ARCHS[arch]
    orig_overrides = dict(overrides) if overrides else None
    if overrides:
        overrides = dict(overrides)
        moe_keys = {k: overrides.pop(k) for k in list(overrides)
                    if cfg.moe is not None and hasattr(cfg.moe, k)}
        ssm_keys = {k: overrides.pop(k) for k in list(overrides)
                    if cfg.ssm is not None and hasattr(cfg.ssm, k) and not hasattr(cfg, k)}
        if moe_keys:
            cfg = replace(cfg, moe=replace(cfg.moe, **moe_keys))
        if ssm_keys:
            cfg = replace(cfg, ssm=replace(cfg.ssm, **ssm_keys))
        if overrides:
            cfg = replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)

    # auto-fit: escalate the remat policy until the cell fits 16 GB HBM
    policies = [cfg.remat] + [p for p in ("full",) if p != cfg.remat and shape.kind == "train"]
    mem_bytes, used_policy = None, cfg.remat
    for policy in policies:
        cfg_try = replace(cfg, remat=policy)
        layout, compiled, t_lower, t_compile = _compile_cell(cfg_try, shape, mesh)
        mem = compiled.memory_analysis()
        mem_bytes = float(getattr(mem, "temp_size_in_bytes", 0) or 0) + float(
            getattr(mem, "argument_size_in_bytes", 0) or 0
        )
        used_policy = policy
        if mem_bytes <= HBM_PER_CHIP:
            break

    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    walked = hlo_analyze(hlo)

    n_params = PM.param_count(layout)
    embed_params = cfg.vocab * cfg.d_model
    active = None
    if cfg.moe is not None:
        # active params: replace routed-expert params with top_k worth
        E, K = cfg.moe.n_experts, cfg.moe.top_k
        expert_params = 3 * cfg.d_model * cfg.moe.d_expert
        routed_total = (cfg.n_layers - (1 if cfg.moe.first_dense else 0)) * E * expert_params
        active = n_params - routed_total + routed_total * K // E

    chips = mesh.devices.size
    report = RooflineReport(
        arch=arch,
        shape=shape_name,
        mesh=mesh_name,
        chips=chips,
        hlo_flops_per_chip=float(walked["flops"]),
        hlo_bytes_per_chip=float(walked["traffic_bytes"]),
        collective_bytes_per_chip=float(walked["collective_total"]),
        collectives=walked["collectives"],
        model_flops=model_flops(cfg, shape, n_params, embed_params, active),
        memory_per_device=mem_bytes or 0.0,
    )
    result = {
        "status": "ok",
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "n_params": n_params,
        "remat": used_policy,
        "fits_hbm": bool(mem_bytes is not None and mem_bytes <= HBM_PER_CHIP),
        "xla_cost_flops_per_chip": float(cost.get("flops", 0.0)),
        "memory_analysis": str(mem),
        **report.to_dict(),
    }
    if save:
        os.makedirs(RESULTS_DIR, exist_ok=True)
        tag = f"{arch}__{shape_name}__{mesh_name}"
        if orig_overrides:
            tag += "__" + "_".join(f"{k}-{v}" for k, v in sorted(orig_overrides.items()))
        with open(os.path.join(RESULTS_DIR, f"{tag}.json"), "w") as fh:
            json.dump(result, fh, indent=1, default=str)
    return result


def main():
    os.environ["XLA_FLAGS"] = " ".join(
        [os.environ.get("XLA_FLAGS", ""), "--xla_force_host_platform_device_count=512"]
    ).strip()
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=sorted(ARCHS))
    ap.add_argument("--shape", default=None, choices=sorted(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mesh", choices=("single", "multi", "both"), default=None)
    ap.add_argument("--override", action="append", default=[],
                    help="cfg overrides, e.g. --override remat=full")
    args = ap.parse_args()

    overrides = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        overrides[k] = {"True": True, "False": False}.get(v, v)

    meshes = []
    if args.mesh in ("single", "both") or (args.mesh is None and not args.multi_pod):
        meshes.append(False)
    if args.mesh in ("multi", "both") or args.multi_pod:
        meshes.append(True)

    cells = []
    archs = sorted(ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = [s.name for s in ALL_SHAPES] if (args.all or not args.shape) else [args.shape]
    for mp in meshes:
        for a in archs:
            for s in shapes:
                cells.append((a, s, mp))

    failures = 0
    for arch, shape, mp in cells:
        tag = f"{arch:24s} {shape:12s} {'2x16x16' if mp else '16x16':8s}"
        try:
            r = run_cell(arch, shape, multi_pod=mp, overrides=overrides or None)
            if r["status"] == "skipped":
                print(f"SKIP {tag} ({r['reason'][:60]})", flush=True)
            else:
                print(
                    f"OK   {tag} compile={r['compile_s']:7.1f}s "
                    f"flops/chip={r['hlo_flops_per_chip']:.3e} "
                    f"coll={r['collective_bytes_per_chip']:.3e}B "
                    f"bottleneck={r['bottleneck']}",
                    flush=True,
                )
        except Exception as err:
            failures += 1
            print(f"FAIL {tag} {type(err).__name__}: {str(err)[:200]}", flush=True)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} dry-run cells failed")


if __name__ == "__main__":
    main()
