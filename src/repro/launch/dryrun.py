import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512"
                           ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede any jax import/initialization: jax locks the device count
#   on first init. The flag is dry-run-only; tests/benches see 1 device.
#   The dry-run and the children it starts stay on the CPU, so they can
#   never hold a chip that another process needs.

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture × input shape) cell and both production meshes
(single-pod 16×16 and multi-pod 2×16×16), ``jit(step).lower(...).compile()``
must succeed with ShapeDtypeStruct stand-ins (no allocation). Memory and
cost analyses plus the collective-op histogram are recorded for
EXPERIMENTS.md §Dry-run and the §Roofline benchmarks.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --all
  PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x7b \
      --shape decode_32k --mesh multi
  PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun.json
"""
import argparse
import json
import re
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..configs import ASSIGNED_ARCHS, SHAPES, get_config
from ..configs.base import ModelConfig, ShapeSpec
from ..runtime import serve
from ..runtime.optim import AdamW
from ..runtime.train import jitted_train_step
from . import specs as SP
from .mesh import make_mesh

_DTYPES = {"f32": 4, "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "s8": 1,
           "u8": 1, "pred": 1, "s16": 2, "u16": 2, "f64": 8, "s64": 8,
           "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1}

#: the pod meshes the dry-run lowers for, on 512 virtual CPU devices
_MESHES = {"single": ((16, 16), ("data", "model")),
           "multi": ((2, 16, 16), ("pod", "data", "model"))}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPES[dt]
    return total


def parse_collectives(hlo_text: str) -> Dict[str, Any]:
    """Histogram of collective ops in the optimized HLO.

    Bytes are the op's result bytes (all-gather: gathered size; all-reduce:
    tensor size). Ops are attributed to ``nested`` when they occur inside a
    non-entry computation (scan/while bodies execute once per trip — the
    roofline multiplies those by the known trip count).
    """
    ops: Dict[str, Dict[str, float]] = {}
    in_entry = False
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY "):
            in_entry = True
            continue
        if line and not line[0].isspace() and "{" in line:
            if not line.startswith("ENTRY"):
                in_entry = False
        stripped = line.strip()
        for op in _COLLECTIVES:
            # match '<shape> op(' or '<shape> op-start(' but not fusions
            if re.search(rf"\) {op}(-start)?\(", stripped) or \
                    re.search(rf"\]{{?[^=]*}}? {op}(-start)?\(", stripped) or \
                    f" {op}(" in stripped or f" {op}-start(" in stripped:
                lhs = stripped.split("=")[0] if "=" in stripped else stripped
                rhs_head = stripped.split("=", 1)[-1].split("(", 1)[0]
                nbytes = _shape_bytes(rhs_head)
                key = op + ("" if in_entry else "@nested")
                rec = ops.setdefault(key, {"count": 0, "bytes": 0})
                rec["count"] += 1
                rec["bytes"] += nbytes
                break
    return ops


def _mem_analysis(compiled) -> Dict[str, Any]:
    try:
        ma = compiled.memory_analysis()
        return {
            "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
            "output_bytes": getattr(ma, "output_size_in_bytes", None),
            "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
            "code_bytes": getattr(ma, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # CPU backend may not implement it
        return {"error": str(e)}


def _cost_analysis(compiled) -> Dict[str, float]:
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        return {k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float)) and (
                    "flops" in k or "bytes" in k or "utilization" not in k)}
    except Exception as e:
        return {"error": str(e)}


# --------------------------------------------------------------------------- #
#  cell construction
# --------------------------------------------------------------------------- #

def decode_path(cfg: ModelConfig, shape: ShapeSpec, mesh) -> str:
    n_pods = mesh.shape.get("pod", 1)
    n_stages = mesh.shape["data"]
    b_pod = shape.global_batch // n_pods
    if shape.global_batch % n_pods:
        return "gspmd"
    if serve.ring_supported(cfg, b_pod, n_stages):
        return "ring"
    return "gspmd"


def lower_cell(arch: str, shape_name: str, mesh, *,
               ring_k: int = 1, microbatch: Optional[int] = None,
               train_style: str = "fsdp", ring_quant: int = 0):
    """Build and lower one cell. Returns (lowered, meta)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    n_pods = mesh.shape.get("pod", 1)
    meta: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                            "mesh": dict(mesh.shape), "kind": shape.kind}

    if shape.kind == "train":
        params = SP.params_shapes(cfg)
        opt = SP.opt_shapes(params)
        batch = SP.batch_shapes(cfg, shape)
        step = jitted_train_step(cfg, mesh, params,
                                 microbatch=microbatch,
                                 has_embeds="embeds" in batch,
                                 style=train_style,
                                 donate=False)
        lowered = step.lower(params, opt, batch)
        meta["path"] = f"gspmd-train({train_style})"
        return lowered, meta

    if shape.kind == "prefill":
        params = SP.params_shapes(cfg)
        ctx = SP.decode_context(cfg, shape)
        cache = SP.cache_shapes(cfg, shape.global_batch, ctx)
        batch = SP.batch_shapes(cfg, shape)
        fn = serve.gspmd_prefill(cfg, mesh, params, cache,
                                 has_embeds="embeds" in batch)
        args = (params, cache, batch["tokens"])
        if "embeds" in batch:
            args = args + (batch["embeds"],)
        lowered = fn.lower(*args)
        meta["path"] = "gspmd-prefill"
        return lowered, meta

    # decode
    path = decode_path(cfg, shape, mesh)
    ctx = SP.decode_context(cfg, shape)
    batch = SP.batch_shapes(cfg, shape)
    if path == "ring":
        n_stages = mesh.shape["data"]
        tp = mesh.shape["model"]
        plan = serve.RingPlan.make(cfg, n_stages, k=ring_k)
        params = SP.ring_params_shapes(cfg, n_stages, plan.k, tp,
                                       quant=ring_quant)
        cache = SP.cache_shapes(cfg, shape.global_batch // n_pods, ctx,
                                ring=(n_stages, plan.k))
        step = serve.build_ring_serve_step(cfg, mesh, plan)(params, cache)
        # tokens/ln are per-pod shards stacked back to global batch
        tok = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
        ln = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
        if n_pods > 1:
            cache = SP.cache_shapes(cfg, shape.global_batch, ctx,
                                    ring=(n_stages, plan.k))
        lowered = step.lower(tok, ln, params, cache)
        q = f",q{ring_quant}" if ring_quant else ""
        meta["path"] = f"ring(k={plan.k},w={plan.w},Lpad={plan.L_pad}{q})"
        meta["ring"] = {"k": plan.k, "w": plan.w, "M": n_stages,
                        "L_pad": plan.L_pad, "quant": ring_quant,
                        "n_steps": plan.k * n_stages + n_stages - 1}
        if ring_quant:
            meta["weight_bytes_per_param"] = 0.60   # int4 + bf16/64 scales
        return lowered, meta

    params = SP.params_shapes(cfg)
    cache = SP.cache_shapes(cfg, shape.global_batch, ctx)
    fn = serve.gspmd_decode_step(cfg, mesh, params, cache)
    lowered = fn.lower(params, cache, batch["tokens"])
    meta["path"] = "gspmd-decode"
    return lowered, meta


def run_cell(arch: str, shape_name: str, mesh_kind: str, *,
             ring_k: int = 1, microbatch: Optional[int] = None,
             train_style: str = "fsdp", ring_quant: int = 0,
             keep_text: bool = False) -> Dict[str, Any]:
    mesh = make_mesh(*_MESHES[mesh_kind])
    t0 = time.time()
    lowered, meta = lower_cell(arch, shape_name, mesh, ring_k=ring_k,
                               microbatch=microbatch,
                               train_style=train_style,
                               ring_quant=ring_quant)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    text = compiled.as_text()
    rec = dict(meta)
    rec.update({
        "mesh_kind": mesh_kind,
        "ok": True,
        "lower_s": round(t1 - t0, 2),
        "compile_s": round(t2 - t1, 2),
        "memory": _mem_analysis(compiled),
        "cost": _cost_analysis(compiled),
        "collectives": parse_collectives(text),
    })
    cfg = get_config(arch)
    rec["model"] = {
        "total_params": cfg.total_params(),
        "active_params": cfg.total_active_params(),
        "n_layers": cfg.n_layers,
    }
    if keep_text:
        rec["hlo"] = text
    return rec


def iter_cells():
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        for shape in cfg.shapes():
            yield arch, shape.name


def _run_subprocess(arch, shape, mk, args) -> Dict[str, Any]:
    """One cell in a fresh process: jit caches and compiler RSS are freed
    between cells, and a pathological cell cannot take down the sweep."""
    import subprocess
    import tempfile
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
        tmp = f.name
    cmd = [sys.executable, "-m", "repro.launch.dryrun",
           "--arch", arch, "--shape", shape, "--mesh", mk,
           "--ring-k", str(args.ring_k), "--out", tmp, "--single-process"]
    if args.microbatch:
        cmd += ["--microbatch", str(args.microbatch)]
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)          # child sets its own 512-device flag
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          timeout=3600)
    try:
        with open(tmp) as f:
            recs = json.load(f)
        os.unlink(tmp)
        return recs[0]
    except Exception:
        return {"arch": arch, "shape": shape, "mesh_kind": mk, "ok": False,
                "error": f"subprocess rc={proc.returncode}",
                "stderr": proc.stderr[-1500:]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--ring-k", type=int, default=1)
    ap.add_argument("--microbatch", type=int, default=None)
    ap.add_argument("--out", default="dryrun_results.json")
    ap.add_argument("--single-process", action="store_true",
                    help="run cells in-process (default for single cells)")
    args = ap.parse_args(argv)

    cells = list(iter_cells()) if args.all else [(args.arch, args.shape)]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    in_process = args.single_process or (len(cells) == 1
                                         and len(meshes) == 1)

    results = []
    failures = 0
    for arch, shape in cells:
        for mk in meshes:
            tag = f"{arch} × {shape} × {mk}"
            if in_process:
                try:
                    rec = run_cell(arch, shape, mk, ring_k=args.ring_k,
                                   microbatch=args.microbatch)
                except Exception as e:
                    rec = {"arch": arch, "shape": shape, "mesh_kind": mk,
                           "ok": False,
                           "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-2000:]}
            else:
                rec = _run_subprocess(arch, shape, mk, args)
            if rec.get("ok"):
                ca = rec.get("cost", {})
                print(f"OK   {tag:58s} path={rec['path']} "
                      f"flops={ca.get('flops', float('nan')):.3e} "
                      f"compile={rec.get('compile_s')}s", flush=True)
            else:
                failures += 1
                print(f"FAIL {tag:58s} {rec.get('error')}", flush=True)
            results.append(rec)

    with open(args.out, "w") as f:
        json.dump(results, f, indent=1)
    print(f"\n{len(results) - failures}/{len(results)} cells OK "
          f"-> {args.out}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
