import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks the device count on first
#   init).  This module is the ONLY place the 512 placeholder devices are
#   requested — tests and benches see the real device count.

import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import math          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402
from pathlib import Path  # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ..configs import ARCH_IDS, SHAPES, get_config, input_specs, shape_applicable  # noqa: E402
from ..distributed.analysis import Roofline, model_flops, parse_collectives  # noqa: E402
from ..distributed.sharding import default_rules  # noqa: E402
from ..distributed.steps import make_decode_step, make_prefill_step, make_train_step  # noqa: E402
from ..models.lm import init_params  # noqa: E402
from ..optim.adamw import adamw  # noqa: E402
from .mesh import make_production_mesh  # noqa: E402

# per-arch microbatch counts for train_4k, sized so rematted activations fit
# 16 GB/chip HBM (derivation in EXPERIMENTS.md §Dry-run)
MICROBATCHES = {
    "granite-20b": 8, "qwen3-0.6b": 2, "granite-3-2b": 4, "internlm2-1.8b": 2,
    "deepseek-moe-16b": 4, "qwen3-moe-235b-a22b": 16, "mamba2-780m": 4,
    "internvl2-26b": 8, "musicgen-medium": 4, "recurrentgemma-9b": 8,
}
# archs whose optimizer moments are kept in bf16 to fit HBM (DESIGN.md §5)
BF16_MOMENTS = {"qwen3-moe-235b-a22b"}


def _opt_for(arch: str):
    return adamw(1e-4, moment_dtype=jnp.bfloat16 if arch in BF16_MOMENTS
                 else jnp.float32)


def _lower_compile(cfg, shape, mesh, rules, *, microbatches=1,
                   accum_unroll=False):
    """Build the step for (cfg, shape), jit-lower against ShapeDtypeStructs,
    compile; returns (compiled, per-device cost dict)."""
    specs = input_specs(cfg, shape)
    pshapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    if shape.kind == "train":
        opt = _opt_for(cfg.name.split("-reduced")[0])
        fn, in_sh, out_sh, donate = make_train_step(
            cfg, mesh, opt, rules=rules, microbatches=microbatches,
            sample_batch=specs["batch"], accum_unroll=accum_unroll)
        oshapes = jax.eval_shape(opt.init, pshapes)
        args = (pshapes, oshapes, specs["batch"])
    elif shape.kind == "prefill":
        fn, in_sh, out_sh, donate = make_prefill_step(
            cfg, mesh, cache_len=shape.seq, rules=rules,
            sample_batch=specs["batch"])
        args = (pshapes, specs["batch"])
    else:
        fn, in_sh, out_sh, donate = make_decode_step(
            cfg, mesh, rules=rules, sample_batch=specs["batch"],
            sample_caches=specs["caches"])
        args = (pshapes, specs["batch"], specs["caches"], specs["pos"])
    jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=donate)
    compiled = jitted.lower(*args).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    coll = parse_collectives(compiled.as_text())
    metrics = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": float(coll.total_bytes),
        "coll_by_kind": coll.by_kind,
        "n_coll": coll.count,
    }
    return compiled, metrics


def _probe_cfg(cfg, n_layers):
    return dataclasses.replace(cfg, n_layers=n_layers, scan_layers=False,
                               unroll_scans=True)


def probe_roofline(cfg, shape, mesh, rules, mb_real: int) -> dict:
    """Loop-aware HLO cost via unrolled probe compiles at reduced depth,
    extrapolated affinely to the real depth (and microbatch count for
    training).  Exact for depth-homogeneous models; see EXPERIMENTS.md."""
    p = len(cfg.block_pattern)
    t = cfg.n_tail
    L1, L2 = p + t, 2 * p + t
    L_real = cfg.n_layers

    def probe(L, mb):
        _, m = _lower_compile(_probe_cfg(cfg, L), shape, mesh, rules,
                              microbatches=mb, accum_unroll=True)
        return m

    keys = ("flops", "bytes", "coll")
    if shape.kind == "train" and mb_real > 1:
        f11, f21 = probe(L1, 1), probe(L2, 1)
        f12, f22 = probe(L1, 2), probe(L2, 2)
        out = {}
        for k in keys:
            s1 = (f21[k] - f11[k]) / (L2 - L1)
            s2 = (f22[k] - f12[k]) / (L2 - L1)
            fL1 = f11[k] + s1 * (L_real - L1)   # m = 1 at real depth
            fL2 = f12[k] + s2 * (L_real - L1)   # m = 2 at real depth
            out[k] = fL1 + (mb_real - 1) * (fL2 - fL1)
        out["probe_points"] = {"L1": L1, "L2": L2, "mb": [1, 2]}
        return out
    f1, f2 = probe(L1, 1), probe(L2, 1)
    out = {}
    for k in keys:
        slope = (f2[k] - f1[k]) / (L2 - L1)
        out[k] = f1[k] + slope * (L_real - L1)
    out["probe_points"] = {"L1": L1, "L2": L2}
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: Path,
             rules_override: dict | None = None, microbatches: int | None = None,
             tag: str = "", probes: bool = True) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cell = {"arch": arch, "shape": shape_name, "mesh": mesh_name, "tag": tag}
    if not shape_applicable(cfg, shape_name):
        cell["status"] = "SKIP"
        cell["reason"] = ("long_500k requires sub-quadratic attention; "
                         "full-attention arch skipped per assignment")
        return cell

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = math.prod(mesh.devices.shape)
    rules = default_rules(mesh)
    if rules_override:
        rules = rules.override(**rules_override)

    mb = 1
    if shape.kind == "train":
        dp = math.prod(mesh.shape[a] for a in ("pod", "data")
                       if a in mesh.axis_names)
        per_shard = shape.batch // dp
        mb = max(1, min(microbatches or MICROBATCHES.get(arch, 1), per_shard))
        cell["microbatches"] = mb

    # 1) the REAL compile (scan-stacked layers): proves the distribution
    #    config lowers + compiles; memory_analysis from here
    compiled, raw = _lower_compile(cfg, shape, mesh, rules, microbatches=mb)
    cell["compile_s"] = round(time.time() - t0, 1)
    mem = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                mem[k] = int(v)
        mem["total_per_device_gb"] = round(
            (mem.get("argument_size_in_bytes", 0) +
             mem.get("output_size_in_bytes", 0) +
             mem.get("temp_size_in_bytes", 0) -
             mem.get("alias_size_in_bytes", 0)) / 2**30, 2)
    except Exception as e:  # pragma: no cover
        mem["error"] = repr(e)
    cell["memory_analysis"] = mem
    cell["raw_cost_scan_counted_once"] = raw
    cell["chips"] = chips

    # 2) probe compiles for loop-aware cost (single-pod roofline only)
    if probes and not multi_pod:
        t1 = time.time()
        est = probe_roofline(cfg, shape, mesh, rules, mb)
        cell["probe_s"] = round(time.time() - t1, 1)
        mf = model_flops(cfg, shape.kind, shape.batch, shape.seq)
        rl = Roofline(
            arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
            hlo_flops=est["flops"] * chips, hlo_bytes=est["bytes"] * chips,
            collective_bytes=est["coll"] * chips, model_flops_total=mf,
        ).finalize()
        cell["roofline"] = rl.as_dict()
        cell["probe_points"] = est.get("probe_points")
    cell["status"] = "OK"
    out_dir.mkdir(parents=True, exist_ok=True)
    fname = f"{arch}_{shape_name}_{mesh_name}{('_' + tag) if tag else ''}.json"
    (out_dir / fname).write_text(json.dumps(cell, indent=1))
    return cell


def main() -> None:
    ap = argparse.ArgumentParser(description="multi-pod dry-run: lower+compile "
                                 "every (arch × shape × mesh) cell")
    ap.add_argument("--arch", default=None, choices=ARCH_IDS)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true", help="all archs × shapes")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--rules", default=None,
                    help="comma list of logical=mesh overrides, e.g. "
                         "'embed=model,mlp=data'")
    args = ap.parse_args()

    overrides = None
    if args.rules:
        overrides = {}
        for kv in args.rules.split(","):
            k, _, v = kv.partition("=")
            overrides[k] = None if v in ("", "none", "None") else (
                tuple(v.split("+")) if "+" in v else v)

    archs = ARCH_IDS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    out = Path(args.out)

    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                label = f"{arch} × {shape} × {'2x16x16' if mp else '16x16'}"
                try:
                    cell = run_cell(arch, shape, mp, out, overrides,
                                    args.microbatches, args.tag,
                                    probes=not args.no_probes)
                    status = cell["status"]
                    extra = ""
                    if status == "OK":
                        extra = (f" mem={cell['memory_analysis'].get('total_per_device_gb', '?')}GB"
                                 f" compile={cell['compile_s']}s")
                        if "roofline" in cell:
                            r = cell["roofline"]
                            extra += (f" compute={r['compute_s']*1e3:.1f}ms"
                                      f" memory={r['memory_s']*1e3:.1f}ms"
                                      f" coll={r['collective_s']*1e3:.1f}ms"
                                      f" bound={r['bottleneck']}"
                                      f" useful={r['useful_ratio']:.2f}")
                except Exception:
                    status = "FAIL"
                    extra = "\n" + traceback.format_exc(limit=8)
                    cell = {"arch": arch, "shape": shape,
                            "mesh": "2x16x16" if mp else "16x16",
                            "status": "FAIL", "error": traceback.format_exc()}
                    out.mkdir(parents=True, exist_ok=True)
                    (out / f"{arch}_{shape}_{cell['mesh']}_FAIL.json").write_text(
                        json.dumps(cell, indent=1))
                results.append(cell)
                print(f"[{status}] {label}{extra}", flush=True)

    n_ok = sum(1 for c in results if c["status"] == "OK")
    n_skip = sum(1 for c in results if c["status"] == "SKIP")
    n_fail = sum(1 for c in results if c["status"] == "FAIL")
    print(f"\ndry-run: {n_ok} OK, {n_skip} SKIP (documented), {n_fail} FAIL")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
