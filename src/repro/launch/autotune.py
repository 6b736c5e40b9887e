import os
# placeholder CPU devices stand in for the pod: never touch an attached chip
os.environ["JAX_PLATFORMS"] = "cpu"
if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count="
                               + os.environ.get("REPRO_DRYRUN_DEVICES",
                                                "256")).strip()

# ARCO over the pod: measurement oracle = lower + compile + roofline.
#
#     PYTHONPATH=src python -m repro.launch.autotune \
#         --arch mixtral-8x22b --shape train_4k --budget 14
#
# This is the beyond-paper §Perf engine: the same MAPPO+CS machinery from
# the paper, pointed at the 256-chip execution configuration, where each
# "hardware measurement" costs an SPMD compile (tens of seconds) — the cost
# regime Confidence Sampling was designed for.  ``search`` is a thin adapter
# over ``repro.compiler.Session`` + ``CompileOracle``; only the heavy
# measurement itself (``compile_and_analyze``) lives here.

import argparse
import json
import time
from typing import Dict

import jax

from repro.configs import get_config
from repro.configs.shapes import SHAPES, input_specs
from repro.core import mappo
from repro.core.tuner import TunerConfig
from repro.hw import hlo_analysis, roofline as RL
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.train import steps as ST


def compile_and_analyze(arch: str, shape_name: str,
                        settings: Dict[str, object],
                        verbose: bool = True) -> Dict[str, object]:
    """One 'hardware measurement': build the cell under ``settings``,
    compile for the pod mesh, return roofline numbers."""
    import jax.numpy as jnp
    cfg = get_config(arch).with_(
        attn_chunk=int(settings["attn_chunk"]),
        remat=bool(settings["remat"]))
    cell = SHAPES[shape_name]
    n_dev = len(jax.devices())
    model_axis = int(settings["model_axis"])
    data_axis = max(n_dev // model_axis, 1)
    mesh = make_host_mesh(data_axis, model_axis)

    from repro.dist.sharding import ShardingRules
    rules = ShardingRules(
        fsdp_weights=bool(settings["fsdp"]),
        sequence_parallel=bool(settings.get("sequence_parallel", False)))
    abstract = T.abstract_params(jax.random.PRNGKey(0), cfg)
    spec = input_specs(cfg, cell)
    t0 = time.time()
    with mesh:
        if cell.kind == "train":
            tc = ST.TrainConfig(
                grad_accum=int(settings.get("grad_accum", 1)),
                moment_dtype=jnp.float32
                if settings["moment_dtype"] == "float32" else jnp.bfloat16)
            jitted, _ = ST.build_sharded_train_step(
                cfg, tc, mesh, rules=rules, abstract_params=abstract)
            opt = ST.make_optimizer(tc)
            lowered = jitted(spec).lower(
                abstract, jax.eval_shape(opt.init, abstract), spec)
        elif cell.kind == "prefill":
            jitted, _ = ST.build_sharded_prefill(
                cfg, mesh, max_len=cell.seq, rules=rules,
                abstract_params=abstract)
            lowered = jitted(spec).lower(abstract, spec)
        else:
            jitted, _ = ST.build_sharded_serve_step(
                cfg, mesh, rules=rules, abstract_params=abstract,
                abstract_cache=spec["cache"], batch=cell.global_batch,
                max_len=cell.seq)
            lowered = jitted.lower(abstract, spec["cache"], spec["tokens"])
        compiled = lowered.compile()
    weighted = hlo_analysis.analyze(compiled.as_text())
    art = {"weighted": {
        "dot_flops_per_device": weighted["weighted_dot_flops"],
        "wire_bytes_per_device": weighted["wire_bytes_per_device"],
        "collective_bytes_by_op": weighted["collective_bytes_by_op"]}}
    r = RL.analyze_cell(cfg, cell.kind, cell.seq, cell.global_batch,
                        dict(mesh.shape), art)
    # Eq. 4/5 analog: hinge penalty on modelled HBM overflow — an OOM
    # configuration must never win the search.
    res = RL.hbm_residency(
        cfg, cell.kind, cell.seq, cell.global_batch, dict(mesh.shape),
        fsdp=bool(settings["fsdp"]),
        moment_dtype=str(settings["moment_dtype"]),
        remat=bool(settings["remat"]),
        grad_accum=int(settings.get("grad_accum", 1)),
        sequence_parallel=bool(settings.get("sequence_parallel", False)))
    hbm = 16 * 2.0 ** 30
    overflow_gib = max(res - hbm, 0.0) / 2.0 ** 30
    step_pen = r.step_s * (1.0 + overflow_gib) + overflow_gib
    out = dict(r.as_dict(), compile_s=time.time() - t0,
               settings=dict(settings),
               hbm_residency_gib=res / 2.0 ** 30,
               feasible=res <= hbm, step_penalized_s=step_pen)
    if verbose:
        print(f"  measure {settings}: step={r.step_s:.4f}s "
              f"residency={res / 2.0 ** 30:.1f}GiB "
              f"{'ok' if res <= hbm else 'OOM'} "
              f"dominant={r.dominant} (compile {out['compile_s']:.0f}s)",
              flush=True)
    jax.clear_caches()
    return out


def search(arch: str, shape_name: str, budget: int = 14,
           seed: int = 0, out_path: str = None,
           records_path: str = None,
           workers: int = 0, timeout_s: float = None,
           remote: str = None, trace: str = None,
           monitor=None, trace_sample_rate: float = 1.0):
    """Thin adapter over the session API: one compile-oracle cell, measured
    through ``CompileOracle``.  Re-measures from scratch unless the caller
    opts into persistence with ``records_path`` (JSONL), from which a re-run
    resumes warm — never derived implicitly, so a plain re-run after a code
    or toolchain change always reflects fresh measurements.

    ``workers=N`` fans the tens-of-seconds compiles across N spawned
    measurement workers (each with its own jax init against the same
    pinned device count); ``timeout_s`` bounds each compile — a hung or
    crashed worker records the failure-penalty row and the pool respawns,
    so the search never wedges on one bad configuration.  ``remote=
    "host:port[,host:port]"`` fans the same compiles over TCP worker
    daemons instead of local processes (mutually exclusive with
    ``workers``)."""
    from repro.compiler import Session, TuningTask
    cfg = TunerConfig(
        iteration_opt=max(budget // 4, 2), b_measure=4,
        episodes_per_iter=2,
        mappo=mappo.MappoConfig(n_steps=32, n_envs=8), gbt_rounds=12,
        seed=seed)
    task = TuningTask.cell(arch, shape_name, n_devices=len(jax.devices()))
    result = Session(task, tuner=cfg, budget=budget, records=records_path,
                     workers=workers, timeout_s=timeout_s,
                     remote=remote, trace=trace, monitor=monitor,
                     trace_sample_rate=trace_sample_rate).run().single
    summary = {
        "arch": arch, "shape": shape_name,
        "best_settings": result.best_settings,
        "best_step_s": result.best_latency,
        "n_measurements": result.n_measurements,
        "wall_s": result.wall_time_s,
        "history": [list(r) for r in result.history],
        "oracle": result.oracle_stats,
        "records": records_path,
        "workers": workers,
        "remote": remote,
    }
    if out_path:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(summary, f, indent=1)
    return summary


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--budget", type=int, default=14)
    ap.add_argument("--out", default=None)
    ap.add_argument("--records", default=None,
                    help="JSONL measurement records (persist + warm resume)")
    from repro.compiler.executor import add_worker_args, validate_worker_args
    add_worker_args(ap)
    args = ap.parse_args()
    validate_worker_args(ap, args)
    s = search(args.arch, args.shape, args.budget, out_path=args.out,
               records_path=args.records, workers=args.workers,
               timeout_s=args.timeout_s, remote=args.remote,
               trace=args.trace, monitor=args.monitor,
               trace_sample_rate=args.trace_sample_rate)
    print(json.dumps(s, indent=1))


if __name__ == "__main__":
    main()
