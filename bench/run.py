"""The benchmark's one command: one run of one cell on the chips of this
machine.

    python3 bench/run.py --workload qwen2-1.5b.chat --seed 7 --seconds 40 \\
        --trace 0

Everything is found by name from ``BENCHMARK.json``: the cell names a
configuration (``bench/configs/<config>.json``, which names its driver,
``bench/drivers/<path>.py``) and a traffic mix
(``bench/traffic/<mix>.json``); each per-layer metric is
``bench/metrics/<metric>.json``, which names a reader
(``bench/readers/<reader>.py``) and that reader's arguments.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` traces
the window with the JAX profiler and prints its per-layer metrics. The last
line of standard output is one JSON object; the numbers that decide
``correct`` are printed beside their limits as the last lines of standard
error and under ``checks``, the last key of that object. A run that finds
no TPU, or fewer chips than the cell asks for, exits with 2 and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402

from bench.common import BENCH_DIR, CompileLog, Run, load_json, peaks  # noqa: E402,E501


def cell(name: str) -> dict:
    """The cell, its configuration file and mix, and its metric entries."""
    spec = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; one of {sorted(cells)}")
    w = cells[name]
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])

    def mine(m):
        return name in m.get("workloads", cells)

    return {"workload": w,
            "config": load_json(ROOT, conf["file"]),
            "mix": load_json(BENCH_DIR, "traffic", w["traffic"] + ".json"),
            "end_to_end": [m for m in spec["end_to_end"] if mine(m)],
            "per_layer": [m for m in spec["per_layer"] if mine(m)]}


def device_info(chips: int) -> dict:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: the first JAX device is {devs[0].platform!r}, not a "
              f"TPU; the benchmark does not fall back", file=sys.stderr)
        raise SystemExit(2)
    if len(devs) < chips:
        print(f"bench: the cell needs {chips} chips, found {len(devs)}",
              file=sys.stderr)
        raise SystemExit(2)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def execute(c: dict, seed: int, seconds: float, trace: bool,
            reduced: bool = False) -> tuple:
    """Set up, run the window and check the result; returns the path driver's
    ``Run`` and the set-up seconds. ``reduced`` runs the program's small
    variant of the configuration (tests on the CPU)."""
    log = CompileLog()
    marks = {}
    ctx = types.SimpleNamespace(
        config=c["config"], mix=c["mix"], seed=seed, seconds=seconds,
        trace=trace, reduced=reduced, compile_log=log,
        open_window=lambda: marks.setdefault(
            "setup_s", time.perf_counter() - T_START))
    driver = importlib.import_module(f"bench.drivers.{c['config']['path']}")
    run: Run = driver.run(ctx)
    run.notes["compiles_total"] = log.n
    run.notes["cache_hits"] = log.hits
    return run, marks["setup_s"]


def read_metrics(c: dict, run: Run, setup_s: float, trace: bool,
                 device: dict) -> dict:
    out = {}
    if not trace:
        values = dict(run.metrics, setup_s=setup_s)
        for m in c["end_to_end"]:
            out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
        return out
    pk = peaks(device["kind"])
    for m in c["per_layer"]:
        spec = load_json(BENCH_DIR, "metrics", m["name"] + ".json")
        reader = importlib.import_module(f"bench.readers.{spec['reader']}")
        v = reader.read(run, c["config"], pk, **spec.get("args", {}))
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    c = cell(args.workload)
    device = device_info(c["workload"]["chips"])
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # every program goes to the cache, however fast it compiled, so that
    # a second run of the cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    run, setup_s = execute(c, args.seed, args.seconds, bool(args.trace))
    metrics = read_metrics(c, run, setup_s, bool(args.trace), device)

    device["memory_peak_bytes"] = run.memory_peak_bytes
    if args.trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
    notes = dict(run.notes, setup_s=setup_s)
    print("bench: " + json.dumps(notes), file=sys.stderr)
    doc = {"correct": all(k.ok for k in run.checks),
           "attempted": run.attempted, "failed": run.failed,
           "metrics": metrics, "device": device}
    if args.trace:
        doc["breakdown"] = {k: run.trace[k]
                            for k in ("device_ops", "idle_gaps")}
    doc["checks"] = {k.name: {"value": k.value, "limit": k.limit}
                     for k in run.checks}
    for k in run.checks:
        print(f"check {k.name}: {k.value!r} (limit {k.limit!r}) "
              f"{'ok' if k.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(doc), flush=True)


if __name__ == "__main__":
    main()
