import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede every other import: JAX locks the platform and the device
# count on first init. The dry run compiles for 512 placeholder CPU devices
# and never takes an accelerator, even on a host that has one.

"""Multi-pod dry-run: lower + compile every (architecture x input-shape) on
the production meshes and extract roofline inputs from the compiled artifact.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh single,multi --out benchmarks/results/dryrun.json

Results are flushed after every pair (resumable; pass --force to redo).
No arrays are ever allocated: inputs are ShapeDtypeStructs and only
.lower()/.compile() run.
"""
import argparse
import json
import re
import time
import traceback
from pathlib import Path

import dataclasses

import jax

from repro.configs.base import ARCH_IDS, INPUT_SHAPES, get_config
from repro.launch import mesh as mesh_lib
from repro.launch.steps import jitted_step
from repro.models import sharding as S

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> dict:
    """Per-device bytes moved by each collective family, from compiled HLO.

    Sums *operand* sizes of all-gather / all-reduce / reduce-scatter /
    all-to-all / collective-permute ops (post-SPMD shapes, i.e. per device).
    """
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if "=" not in stripped:
            continue
        rhs = stripped.split("=", 1)[1]
        for op in _COLLECTIVES:
            tok = f" {op}(" if f" {op}(" in rhs else (f" {op}-start(" if f" {op}-start(" in rhs else None)
            if tok is None:
                continue
            pre, _, args = rhs.partition(tok)
            # operand shapes are printed inline in post-opt HLO; if absent,
            # fall back to the output shape (exact for all-reduce/permute).
            arg_str = args.split("),", 1)[0]
            shapes = _SHAPE_RE.findall(arg_str)
            if not shapes:
                shapes = _SHAPE_RE.findall(pre)
            out[op] += sum(_shape_bytes(dt, dims) for dt, dims in shapes)
            counts[op] += 1
            break
    out["counts"] = counts
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def analytic_model_flops(cfg, shape) -> float:
    """MODEL_FLOPS: 6*N*D for training, 2*N*D for fwd-only (N = active params,
    D = tokens processed)."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        toks = shape.global_batch * shape.seq_len
        return 6.0 * n * toks
    if shape.kind == "prefill":
        toks = shape.global_batch * shape.seq_len
        return 2.0 * n * toks
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def _compile_once(cfg, shape, mesh, **kw) -> tuple[dict, object]:
    t0 = time.time()
    fn, args = jitted_step(cfg, shape, mesh, **kw)
    lowered = fn.lower(*args)
    t1 = time.time()
    compiled = lowered.compile()
    rec = {"lower_s": round(t1 - t0, 1), "compile_s": round(time.time() - t1, 1)}
    try:
        mem = compiled.memory_analysis()
        rec["memory"] = {k: int(getattr(mem, k)) for k in
                         ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "alias_size_in_bytes")
                         if hasattr(mem, k)}
    except Exception as e:
        rec["memory"] = {"error": repr(e)}
    try:
        cost = compiled.cost_analysis()
        rec["cost"] = {k: float(v) for k, v in cost.items()
                       if isinstance(v, (int, float)) and k in
                       ("flops", "bytes accessed", "transcendentals")}
    except Exception as e:
        rec["cost"] = {"error": repr(e)}
    rec["collectives"] = collective_bytes(compiled.as_text())
    return rec, compiled


def _probe_depths(cfg) -> tuple[int, int]:
    """Two small depths for per-layer cost extraction; hybrid archs need
    multiples of attn_every so shared-attention sites scale linearly."""
    if cfg.arch_type == "hybrid":
        return cfg.attn_every, 2 * cfg.attn_every
    return 2, 4


def _lin_extrapolate(v1: float, v2: float, l1: int, l2: int, L: int) -> float:
    per_layer = (v2 - v1) / (l2 - l1)
    base = v1 - l1 * per_layer
    return base + L * per_layer


def run_pair(arch: str, shape_name: str, multi_pod: bool,
             variant: dict | None = None) -> dict:
    """Three compiles per pair:
      1. FULL config, production scan-over-layers — proves the real
         (arch x shape x mesh) lowers + compiles; memory_analysis of the
         production artifact.
      2./3. small unrolled depths L1 < L2 — XLA cost analysis counts
         while-loop bodies once, so scanned stacks under-report flops/bytes/
         collectives by ~num_layers; unrolled probes give exact per-layer
         terms which we extrapolate linearly to the full depth.
    """
    variant = variant or {}
    cfg = get_config(arch)
    if variant.get("param_dtype") == "bf16":
        import jax.numpy as jnp
        cfg = dataclasses.replace(cfg, param_dtype=jnp.bfloat16)
    if variant.get("kv_quant"):
        cfg = dataclasses.replace(cfg, kv_cache_quant=True)
    if variant.get("pad_heads"):
        cfg = dataclasses.replace(cfg, n_heads=variant["pad_heads"],
                                  head_dim=cfg.resolved_head_dim)
    kw = {"microbatches": variant.get("microbatches", 1),
          "fsdp_params": variant.get("fsdp_params", True)}
    shape = INPUT_SHAPES[shape_name]
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    rec: dict = {"arch": arch, "shape": shape_name,
                 "mesh": "2x16x16" if multi_pod else "16x16",
                 "devices": mesh.size, "variant": variant}
    with mesh:
        full, _ = _compile_once(cfg, shape, mesh, **kw)
        rec["full"] = full

        l1, l2 = _probe_depths(cfg)
        probes = {}
        for li in (l1, l2):
            pcfg = dataclasses.replace(cfg, num_layers=li, unroll=True)
            probes[li], _ = _compile_once(pcfg, shape, mesh, **kw)
        rec["probes"] = {str(k): v for k, v in probes.items()}

        L = cfg.num_layers
        extr: dict = {}
        for key in ("flops", "bytes accessed"):
            try:
                extr[key] = _lin_extrapolate(probes[l1]["cost"][key],
                                             probes[l2]["cost"][key], l1, l2, L)
            except Exception:
                pass
        try:
            extr["collective_bytes"] = _lin_extrapolate(
                probes[l1]["collectives"]["total"],
                probes[l2]["collectives"]["total"], l1, l2, L)
            extr["collectives_by_kind"] = {
                k: _lin_extrapolate(probes[l1]["collectives"][k],
                                    probes[l2]["collectives"][k], l1, l2, L)
                for k in _COLLECTIVES}
        except Exception:
            pass
        rec["extrapolated"] = extr

    rec["model_flops"] = analytic_model_flops(cfg, shape)
    rec["param_count"] = cfg.param_count()
    rec["active_param_count"] = cfg.active_param_count()
    rec["tokens"] = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    rec["ok"] = True
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single,multi")
    ap.add_argument("--out", default="benchmarks/results/dryrun.json")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="", help="variant key suffix")
    ap.add_argument("--param-dtype", default="", choices=["", "bf16"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--no-fsdp-params", action="store_true")
    ap.add_argument("--kv-quant", action="store_true")
    ap.add_argument("--pad-heads", type=int, default=0,
                    help="pad n_heads to this count (TP head padding)")
    args = ap.parse_args()
    variant = {}
    if args.kv_quant:
        variant["kv_quant"] = True
    if args.pad_heads:
        variant["pad_heads"] = args.pad_heads
    if args.param_dtype:
        variant["param_dtype"] = args.param_dtype
    if args.microbatches != 1:
        variant["microbatches"] = args.microbatches
    if args.no_fsdp_params:
        variant["fsdp_params"] = False

    archs = ARCH_IDS if args.arch == "all" else args.arch.split(",")
    shapes = list(INPUT_SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = args.mesh.split(",")

    out_path = Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    results = json.loads(out_path.read_text()) if out_path.exists() else {}

    for mesh_kind in meshes:
        for arch in archs:
            for shape_name in shapes:
                key = f"{arch}|{shape_name}|{mesh_kind}"
                if args.tag:
                    key += f"|{args.tag}"
                if key in results and results[key].get("ok") and not args.force:
                    print(f"[skip] {key}")
                    continue
                print(f"[dryrun] {key} ...", flush=True)
                try:
                    rec = run_pair(arch, shape_name, mesh_kind == "multi",
                                   variant)
                except Exception:
                    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                           "ok": False, "error": traceback.format_exc()[-2000:]}
                    print(rec["error"], flush=True)
                results[key] = rec
                out_path.write_text(json.dumps(results, indent=1))
                status = "OK" if rec.get("ok") else "FAIL"
                full = rec.get("full", {})
                print(f"[dryrun] {key} {status} "
                      f"compile={full.get('compile_s')}s "
                      f"coll={rec.get('extrapolated', {}).get('collective_bytes')}",
                      flush=True)

    n_ok = sum(1 for r in results.values() if r.get("ok"))
    print(f"done: {n_ok}/{len(results)} pairs OK")


if __name__ == "__main__":
    main()
