"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production meshes and record memory/cost/collective analyses.

  JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.dryrun \
      [--arch A] [--shape S] [--mesh single|multi|both] [--out results/]

The meshes are 512 fake CPU devices, which ``main`` asks for before JAX
initialises.

Proves (assignment deliverable (e)): the distribution config is coherent —
.lower().compile() succeeds for the 16×16 (256-chip) single-pod mesh AND the
2×16×16 (512-chip) multi-pod mesh for every cell; memory_analysis shows it
fits; cost_analysis + HLO collective parsing feed §Roofline.
"""
import argparse
import json
import os
import re
import time
import traceback
from typing import Any, Dict, Optional

import jax
import numpy as np

from repro.compat import request_host_devices
from repro.configs import registry
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import build_cell

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}
_SHAPE_RE = re.compile(r"(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64|f64|c64|c128)\[([0-9,]*)\]")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(token_dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[token_dtype]


def parse_collective_bytes(hlo_text: str) -> Dict[str, Any]:
    """Sum per-op result bytes of every collective in the optimised HLO.

    Result-shape convention: for all-gather/all-to-all the result is the
    received buffer; for all-reduce it equals the operand; reduce-scatter's
    result understates by ~(n-1)/n — acceptable for a roofline term.
    """
    per_kind: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    counts: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        if "=" not in s:
            continue
        for kind in _COLLECTIVES:
            if f" {kind}(" in s or f" {kind}-start(" in s:
                # result type sits between '=' and the op name
                rhs = s.split("=", 1)[1]
                head = rhs.split(f" {kind}", 1)[0]
                m = _SHAPE_RE.findall(head)
                if not m:
                    continue
                # tuples (e.g. -start ops) repeat in/out buffers: take the
                # largest component = the received buffer
                bytes_ = max(_shape_bytes(dt, dims) for dt, dims in m)
                per_kind[kind] += bytes_
                counts[kind] += 1
                break
    total = sum(per_kind.values())
    return {"total_bytes": total, "per_kind_bytes": per_kind,
            "op_counts": counts}


def run_cell(cell, mesh, mesh_name: str) -> Dict[str, Any]:
    t0 = time.time()
    rec: Dict[str, Any] = {
        "arch": cell.arch_id, "shape": cell.shape_name, "mesh": mesh_name,
        "family": cell.family,
    }
    if cell.skip:
        rec["status"] = "SKIP"
        rec["skip_reason"] = cell.skip
        return rec
    try:
        spec = build_cell(cell, mesh)
        with mesh:
            jitted = jax.jit(spec.fn,
                             in_shardings=spec.in_shardings,
                             out_shardings=spec.out_shardings,
                             donate_argnums=spec.donate_argnums)
            lowered = jitted.lower(*spec.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        rec["status"] = "OK"
        rec["lower_s"] = round(t_lower, 1)
        rec["compile_s"] = round(t_compile, 1)
        rec["static_info"] = spec.static_info
        try:
            ma = compiled.memory_analysis()
            rec["memory"] = {
                "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
                "output_bytes": getattr(ma, "output_size_in_bytes", None),
                "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
                "generated_code_bytes": getattr(ma, "generated_code_size_in_bytes", None),
                "alias_bytes": getattr(ma, "alias_size_in_bytes", None),
            }
        except Exception as e:                                  # noqa: BLE001
            rec["memory"] = {"error": str(e)}
        try:
            ca = compiled.cost_analysis()
            rec["cost"] = {k: float(v) for k, v in ca.items()
                           if isinstance(v, (int, float)) and (
                               k in ("flops", "bytes accessed")
                               or k.startswith("bytes accessed"))}
            rec["cost"]["flops"] = float(ca.get("flops", 0.0))
        except Exception as e:                                  # noqa: BLE001
            rec["cost"] = {"error": str(e)}
        try:
            hlo = compiled.as_text()
            rec["collectives"] = parse_collective_bytes(hlo)
            rec["hlo_bytes"] = len(hlo)
        except Exception as e:                                  # noqa: BLE001
            rec["collectives"] = {"error": str(e)}
    except Exception as e:                                      # noqa: BLE001
        rec["status"] = "FAIL"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["wall_s"] = round(time.time() - t0, 1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results")
    ap.add_argument("--force", action="store_true",
                    help="recompute cached cells")
    args = ap.parse_args()
    if not request_host_devices(512):
        raise SystemExit("the dry run compiles for 512 fake CPU devices; "
                         "run it with JAX_PLATFORMS=cpu")
    os.makedirs(args.out, exist_ok=True)

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single_pod_256", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi_pod_512", make_production_mesh(multi_pod=True)))

    cells = registry.all_cells()
    if args.arch:
        cells = [c for c in cells if c.arch_id == args.arch]
    if args.shape:
        cells = [c for c in cells if c.shape_name == args.shape]

    for mesh_name, mesh in meshes:
        out_path = os.path.join(args.out, f"dryrun_{mesh_name}.json")
        results: Dict[str, Any] = {}
        if os.path.exists(out_path):
            with open(out_path) as f:
                results = json.load(f)   # --force recomputes selected cells
                                         # but never discards other entries
        for cell in cells:
            key = f"{cell.arch_id}:{cell.shape_name}"
            if key in results and results[key].get("status") == "OK" and not args.force:
                print(f"[{mesh_name}] {key}: cached OK", flush=True)
                continue
            print(f"[{mesh_name}] {key}: compiling ...", flush=True)
            rec = run_cell(cell, mesh, mesh_name)
            results[key] = rec
            with open(out_path, "w") as f:
                json.dump(results, f, indent=1)
            status = rec["status"]
            extra = ""
            if status == "OK":
                coll = rec.get("collectives", {}).get("total_bytes", 0)
                extra = (f" compile={rec['compile_s']}s"
                         f" flops={rec.get('cost', {}).get('flops', 0):.3g}"
                         f" coll={coll / 1e9:.2f}GB")
            elif status == "FAIL":
                extra = " " + rec.get("error", "")[:200]
            print(f"[{mesh_name}] {key}: {status}{extra}", flush=True)

    # summary
    for mesh_name, _ in meshes:
        out_path = os.path.join(args.out, f"dryrun_{mesh_name}.json")
        with open(out_path) as f:
            results = json.load(f)
        ok = sum(1 for r in results.values() if r["status"] == "OK")
        skip = sum(1 for r in results.values() if r["status"] == "SKIP")
        fail = sum(1 for r in results.values() if r["status"] == "FAIL")
        print(f"== {mesh_name}: {ok} OK / {skip} SKIP / {fail} FAIL "
              f"of {len(results)}")


if __name__ == "__main__":
    main()
