import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_XLA_EXTRA", "") +
                           " --xla_force_host_platform_device_count="
                           + os.environ.get("REPRO_DRYRUN_DEVICES", "512")
                           ).strip()
# ^ MUST run before any other import: jax locks the device count on first
#   initialization.  512 placeholder host devices stand in for 2 pods x 256
#   TPU v5e chips; lowering/compiling against them proves the distribution
#   config (shardings, collectives, memory) is coherent without hardware.
#   The platform is pinned to the CPU so an attached chip is never touched.

# Multi-pod dry-run driver.
#
# For every (architecture x input-shape x mesh) cell:
#     jit(step).lower(abstract inputs)  ->  .compile()
#     -> memory_analysis()  (fits?)  + cost_analysis()  (FLOPs / bytes)
#     -> collective bytes parsed from the partitioned HLO
# and a JSON artifact per cell under --out (EXPERIMENTS.md reads these).
#
# Usage:
#     python -m repro.launch.dryrun --arch qwen2-1.5b --shape train_4k
#     python -m repro.launch.dryrun --all --mesh both --out artifacts/dryrun

import argparse
import json
import re
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import ARCH_NAMES, get_config
from repro.configs.shapes import SHAPES, cell_supported, input_specs
from repro.launch.mesh import describe, make_dryrun_mesh, make_production_mesh
from repro.models import transformer as T
from repro.train import steps as ST
from repro.dist import sharding as SH
from repro.hw import hlo_analysis

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "c64": 8,
                "c128": 16}

_COLL_RE = re.compile(
    r"=\s*(\(?[a-z0-9\[\],{}\s/#_\.]*?\)?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", re.IGNORECASE)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

# wire cost per device, ring-algorithm approximations
_WIRE_MULT = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
              "all-to-all": 1.0, "collective-permute": 1.0}


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo_text: str) -> Dict[str, Any]:
    """Sum result-shape bytes of every collective in partitioned HLO."""
    per_op: Dict[str, int] = {}
    counts: Dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        shapes, op = m.group(1), m.group(2).lower()
        b = _shape_bytes(shapes)
        per_op[op] = per_op.get(op, 0) + b
        counts[op] = counts.get(op, 0) + 1
    wire = sum(_WIRE_MULT[op] * b for op, b in per_op.items())
    return {"bytes_by_op": per_op, "counts": counts,
            "wire_bytes_per_device": wire}


def _while_trip_counts(hlo_text: str):
    """Best-effort trip counts of while loops (scan repeats) so cost numbers
    can be corrected for XLA's single-visit loop accounting."""
    # constants compared in while conditions: look for "trip_count" hints
    out = []
    for m in re.finditer(r'known_trip_count=\{?"?n"?[:=](\d+)', hlo_text):
        out.append(int(m.group(1)))
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: Optional[str] = None,
             batch_override: Optional[int] = None,
             rules: Optional[SH.ShardingRules] = None) -> Dict[str, Any]:
    rules = rules or SH.ShardingRules()
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_dryrun_mesh(multi_pod=multi_pod)
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    result: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "mesh_desc": describe(mesh), "kind": shape.kind,
    }

    ok, reason = cell_supported(cfg, shape)
    if not ok:
        result["status"] = "skipped"
        result["reason"] = reason
        _emit(result, out_dir)
        return result

    t0 = time.time()
    try:
        abstract = T.abstract_params(jax.random.PRNGKey(0), cfg)
        spec = input_specs(cfg, shape, batch_override)
        with mesh:
            if shape.kind == "train":
                tc = ST.TrainConfig()
                jitted, sh = ST.build_sharded_train_step(
                    cfg, tc, mesh, rules=rules, abstract_params=abstract)
                opt = ST.make_optimizer(tc)
                abstract_opt = jax.eval_shape(opt.init, abstract)
                fn = jitted(spec)
                lowered = fn.lower(abstract, abstract_opt, spec)
            elif shape.kind == "prefill":
                jitted, sh = ST.build_sharded_prefill(
                    cfg, mesh, max_len=shape.seq, rules=rules,
                    abstract_params=abstract)
                fn = jitted(spec)
                lowered = fn.lower(abstract, spec)
            else:  # decode
                b = batch_override or shape.global_batch
                jitted, sh = ST.build_sharded_serve_step(
                    cfg, mesh, rules=rules, abstract_params=abstract,
                    abstract_cache=spec["cache"], batch=b,
                    max_len=shape.seq)
                lowered = jitted.lower(abstract, spec["cache"],
                                       spec["tokens"])
            compiled = lowered.compile()

        result["compile_s"] = round(time.time() - t0, 2)
        mem = compiled.memory_analysis()
        if mem is not None:
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes"):
                v = getattr(mem, k, None)
                if v is not None:
                    result[k] = int(v)
        cost = compiled.cost_analysis()
        if cost:
            result["cost_flops"] = float(cost.get("flops", 0.0))
            result["cost_bytes"] = float(cost.get("bytes accessed", 0.0))
        hlo = compiled.as_text()
        result["collectives"] = collective_stats(hlo)   # raw (loop-body once)
        weighted = hlo_analysis.analyze(hlo)            # trip-count weighted
        result["weighted"] = {
            "dot_flops_per_device": weighted["weighted_dot_flops"],
            "collective_bytes_by_op": weighted["collective_bytes_by_op"],
            "wire_bytes_per_device": weighted["wire_bytes_per_device"],
        }
        result["hlo_chars"] = len(hlo)
        result["trip_counts"] = _while_trip_counts(hlo)
        result["status"] = "ok"
        result["param_bytes_global"] = int(sum(
            int(jnp.dtype(l.dtype).itemsize) * int(
                __import__("numpy").prod(l.shape))
            for l in jax.tree.leaves(abstract)))
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        result["status"] = "error"
        result["error"] = f"{type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-4000:]
    _emit(result, out_dir)
    return result


def _emit(result: Dict[str, Any], out_dir: Optional[str]):
    line = (f"[{result['mesh']}] {result['arch']} x {result['shape']}: "
            f"{result['status']}")
    if result["status"] == "ok":
        coll = result["weighted"]["wire_bytes_per_device"]
        line += (f"  dotF/dev={result['weighted']['dot_flops_per_device']:.3e}"
                 f" tempB={result.get('temp_size_in_bytes', 0):.3e}"
                 f" collB/dev={coll:.3e}"
                 f" compile={result['compile_s']}s")
    elif result["status"] == "skipped":
        line += f"  ({result['reason'][:60]}...)"
    else:
        line += f"  {result['error'][:200]}"
    print(line, flush=True)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fname = (f"{result['arch']}__{result['shape']}__"
                 f"{result['mesh']}.json")
        result = dict(result)
        result.pop("traceback", None)
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(result, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"],
                    default="pod")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--batch", type=int, default=None,
                    help="override global batch (debug)")
    ap.add_argument("--sp", action="store_true",
                    help="optimized rules: Megatron-style sequence "
                         "parallelism on the residual stream")
    args = ap.parse_args()
    rules = SH.ShardingRules(sequence_parallel=args.sp)

    archs = ARCH_NAMES if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"pod": [False], "multipod": [True],
              "both": [False, True]}[args.mesh]

    n_bad = 0
    for mp in meshes:
        for arch in archs:
            for shape in shapes:
                r = run_cell(arch, shape, mp, args.out, args.batch,
                             rules=rules)
                n_bad += r["status"] == "error"
    print(f"done; {n_bad} errors", flush=True)
    raise SystemExit(1 if n_bad else 0)


if __name__ == "__main__":
    main()
