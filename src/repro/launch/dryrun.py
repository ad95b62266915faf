import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# NOTE: the two lines above MUST stay first — jax locks the device count on
# first init.  (This also precludes `from __future__ import annotations`.)

DOC = """Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware: the production
meshes (16×16 single-pod, 2×16×16 multi-pod) are built from 512 placeholder
CPU devices (the XLA_FLAGS line above MUST precede any jax import), every
assigned cell is ``.lower().compile()``d, and the compiled artifact yields

  * ``memory_analysis()``  — per-device bytes (proves it fits),
  * ``cost_analysis()``    — per-device HLO FLOPs / bytes accessed,
  * collective bytes       — parsed from the SPMD HLO text,

from which the three roofline terms are derived (TPU v5e constants).
Artifacts land in ``artifacts/dryrun/<mesh>/<arch>__<shape>.json`` and feed
EXPERIMENTS.md §Dry-run / §Roofline.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import json
import re
import time
import traceback
from typing import Dict, Optional

import jax
import numpy as np

from repro.configs import (ARCHS, SHAPES, get_config, get_shape,
                           shape_applicable)
from repro.configs.base import ModelConfig, ShapeConfig
from repro.distributed.hlo_analysis import analyze as analyze_hlo
from repro.distributed.sharding import ShardingRules
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import dummy_args, make_step_for_shape
from repro.models import ExecConfig, build_model
from repro.optim import SGD

# ----------------------------------------------------------------- hardware --
# TPU v5e, per chip.
PEAK_FLOPS = 197e12            # bf16 FLOP/s
HBM_BW = 819e9                 # bytes/s
ICI_BW = 50e9                  # bytes/s per link

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16, "f8e4m3": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"=\s*(?:\()?([a-z0-9]+)\[([0-9,]*)\][^=]*?\s"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum per-device collective op bytes from post-SPMD HLO, by op kind."""
    out: Dict[str, float] = {"all-gather": 0.0, "all-reduce": 0.0,
                             "reduce-scatter": 0.0, "all-to-all": 0.0,
                             "collective-permute": 0.0}
    counts: Dict[str, int] = {k: 0 for k in out}
    for m in _COLL_RE.finditer(hlo_text):
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        if kind.endswith("-done"):
            continue
        nbytes = _DTYPE_BYTES.get(dtype, 4)
        if dims:
            for d in dims.split(","):
                if d:
                    nbytes *= int(d)
        out[kind] += float(nbytes)
        counts[kind] += 1
    out["counts"] = counts            # type: ignore[assignment]
    return out


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """MODEL_FLOPS = 6·N_active·D (train) or 2·N_active·D (inference).

    N counts matmul-involved params: the embedding *lookup* is free, but the
    unembed matmul always costs V·d per token (for tied embeddings the table
    is counted once in active_param_count and used as the unembed matmul)."""
    n = cfg.active_param_count()
    if not cfg.tie_embeddings:
        n -= cfg.vocab_size * cfg.d_model
    mult = 6 if shape.kind == "train" else 2
    return float(mult) * n * shape.tokens_per_step


def exec_for(cfg: ModelConfig, shape: ShapeConfig,
             overrides: Optional[dict] = None) -> ExecConfig:
    """Per-cell execution plan (the §Perf baseline; overrides hillclimb it)."""
    kw: Dict = dict(backend="xla", remat="full", scan_layers=True)
    if shape.kind == "train":
        kw["loss_chunk"] = 512
        if cfg.name == "kimi-k2-1t-a32b":
            # §Perf cell B: microbatches=1 strictly dominates (fewest FSDP
            # weight re-gathers); grads stay bf16 with no accumulator.
            kw["microbatches"] = 1
            kw["moe_group_size"] = 256
            kw["accum_dtype"] = "bfloat16"
        elif cfg.n_experts:
            kw["moe_group_size"] = 256
    else:
        kw["loss_chunk"] = 0
    if overrides:
        kw.update(overrides)
    return ExecConfig(**kw)


def run_cell(arch: str, shape_id: str, mesh, mesh_name: str,
             overrides: Optional[dict] = None, fsdp: bool = True) -> dict:
    cfg = get_config(arch)
    shape = get_shape(shape_id)
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_id, "mesh": mesh_name,
                "status": "skipped", "reason": reason}

    n_dev = mesh.size
    ec = exec_for(cfg, shape, overrides)
    model = build_model(cfg, ec)
    rules = ShardingRules(mesh, cfg, fsdp=fsdp)
    t0 = time.perf_counter()
    with mesh:
        jitted, args = make_step_for_shape(model, rules, shape,
                                           optimizer=SGD(lr=0.01))
        lowered = jitted.lower(*dummy_args(model, shape, args, SGD(lr=0.01)))
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

        mem = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else (ca or {})
        hlo = compiled.as_text()
    # Static HLO analysis: XLA-CPU cost_analysis counts while bodies once, so
    # scanned-layer programs need the trip-count-aware traversal.
    costs = analyze_hlo(hlo)
    hlo_len = len(hlo)
    del hlo, compiled, lowered, jitted

    flops = costs.flops
    bytes_accessed = costs.bytes
    coll = {k: v for k, v in costs.collective.items()}
    coll["counts"] = costs.collective_counts
    coll_total = costs.collective_bytes
    xla_flops = float(ca.get("flops", 0.0))

    # roofline terms, seconds (per-device program => per-chip terms).
    # "corrected" strips XLA-CPU's bf16->f32 emulation traffic/copies, which
    # do not exist on TPU (native bf16).
    t_compute = flops / PEAK_FLOPS
    t_memory = bytes_accessed / HBM_BW
    t_memory_corr = max(0.0, bytes_accessed - costs.bf16_convert_bytes) / HBM_BW
    t_coll = coll_total / ICI_BW
    terms = {"compute": t_compute, "memory": t_memory_corr, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    peak_corr = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes
                 - costs.bf16_convert_static_bytes)

    mf = model_flops(cfg, shape)
    useful_ratio = mf / (flops * n_dev) if flops else 0.0

    rec = {
        "arch": arch, "shape": shape_id, "mesh": mesh_name,
        "status": "ok", "n_devices": n_dev,
        "exec": {k: getattr(ec, k) for k in
                 ("backend", "remat", "moe_impl", "moe_group_size",
                  "microbatches", "loss_chunk", "attn_block_k")},
        "fsdp": fsdp,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "hlo_bytes": hlo_len,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_bytes": (mem.argument_size_in_bytes
                           + mem.output_size_in_bytes
                           + mem.temp_size_in_bytes
                           - mem.alias_size_in_bytes),
            "bf16_emulation_bytes": costs.bf16_convert_static_bytes,
            "peak_bytes_corrected": peak_corr,
        },
        "flops_per_device": flops,
        "bytes_per_device": bytes_accessed,
        "collective_bytes_per_device": coll_total,
        "collectives": coll,
        "xla_cost_flops": xla_flops,
        "analysis_warnings": sorted(set(costs.warnings)),
        "roofline": {
            "t_compute_s": t_compute, "t_memory_s": t_memory,
            "t_memory_corrected_s": t_memory_corr,
            "t_collective_s": t_coll, "dominant": dominant,
            "model_flops": mf,
            "useful_flops_ratio": useful_ratio,
            "roofline_fraction": (t_compute / max(t_compute, t_memory, t_coll)
                                  if max(t_compute, t_memory, t_coll) else 0.0),
        },
    }
    return rec


def main():
    ap = argparse.ArgumentParser(description=DOC)
    ap.add_argument("--arch", default=None, help="arch id (default: all)")
    ap.add_argument("--shape", default=None, help="shape id (default: all)")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--force", action="store_true", help="recompute existing")
    ap.add_argument("--tag", default="", help="artifact suffix (perf variants)")
    ap.add_argument("--override", default="",
                    help="ExecConfig overrides, e.g. 'moe_group_size=512,remat=dots'")
    args = ap.parse_args()

    overrides = {}
    for kv in args.override.split(","):
        if "=" in kv:
            k, v = kv.split("=", 1)
            overrides[k.strip()] = (int(v) if v.strip().lstrip("-").isdigit()
                                    else v.strip())

    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    results = []
    for multi in meshes:
        mesh = make_production_mesh(multi_pod=multi)
        mesh_name = "pod2x16x16" if multi else "pod16x16"
        out_dir = os.path.join(args.out, mesh_name)
        os.makedirs(out_dir, exist_ok=True)
        for arch in archs:
            for shape_id in shapes:
                tag = f"__{args.tag}" if args.tag else ""
                path = os.path.join(out_dir, f"{arch}__{shape_id}{tag}.json")
                if os.path.exists(path) and not args.force:
                    print(f"[skip-cached] {mesh_name} {arch} {shape_id}")
                    continue
                try:
                    rec = run_cell(arch, shape_id, mesh, mesh_name,
                                   overrides=overrides or None,
                                   fsdp=not args.no_fsdp)
                except Exception as e:
                    rec = {"arch": arch, "shape": shape_id, "mesh": mesh_name,
                           "status": "error", "error": repr(e),
                           "traceback": traceback.format_exc()[-4000:]}
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                results.append(rec)
                if rec["status"] == "ok":
                    r = rec["roofline"]
                    print(f"[ok] {mesh_name} {arch:>18s} {shape_id:<12s} "
                          f"compile={rec['compile_s']:7.1f}s "
                          f"peak={rec['memory']['peak_bytes_corrected']/2**30:7.2f}GiB "
                          f"Tc={r['t_compute_s']*1e3:9.3f}ms "
                          f"Tm={r['t_memory_corrected_s']*1e3:9.3f}ms "
                          f"Tx={r['t_collective_s']*1e3:9.3f}ms "
                          f"dom={r['dominant']:<10s} "
                          f"useful={r['useful_flops_ratio']:.3f}", flush=True)
                elif rec["status"] == "skipped":
                    print(f"[skipped] {mesh_name} {arch} {shape_id}: "
                          f"{rec['reason']}", flush=True)
                else:
                    print(f"[ERROR] {mesh_name} {arch} {shape_id}: "
                          f"{rec['error']}", flush=True)
    n_err = sum(1 for r in results if r["status"] == "error")
    print(f"done: {len(results)} cells, {n_err} errors")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
