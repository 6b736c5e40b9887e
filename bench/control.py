"""The readings that the limits of ``correct`` are set from, on the chip:

    python3 bench/control.py --workload qwen2-1.5b.chat --seconds 15 \\
        --seeds 11,12,13 --control-seeds 11,12,13

For every seed in ``--seeds`` it makes one run of the cell at its own
sizes and load (a ``--seconds`` window, the reference check after it) and
prints the numbers compared: the lower readings. For every seed in
``--control-seeds`` it prints the control's readings: the reference in the
precision below the configuration's, in the program's place, read against
the float32 reference the same way, and whether they keep the committed
limits (``correct``, which has to come out false).

- serve (bfloat16 weights): the int8 control ranks the tokens at each
  position of the same sequences the run served and checked, and its
  readings are those of what it ranks first (an fp8 control is read
  beside it); the program's own readings are printed with them.
- train (float32 state): the bfloat16 control follows the same three
  steps from the same weights and rows; beside it, the fault of half the
  batch left out (the mean over the first half) planted in the reference.

Everything runs in one process, so the programs compile once. One JSON
line per reading goes to standard output. The benchmark's own runs never
run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402

from bench import run as bench_run  # noqa: E402
from bench.common import Check  # noqa: E402
from bench.drivers import serve as serve_driver  # noqa: E402
from bench.drivers import train as train_driver  # noqa: E402
from bench.reference import dense  # noqa: E402


def judged(readings: dict, limits: dict) -> dict:
    """A control's readings held to the cell's committed limits, as the
    harness holds the program's: ``correct`` is whether every limited
    number keeps its limit (a control has to come out false)."""
    checks = [Check(k, readings[k], limits[k]) for k in sorted(limits)]
    return dict(readings, correct=all(k.ok for k in checks))


def control_serve(run, seed: int, limits: dict) -> dict:
    cmp = run.compared
    out = {"program": serve_driver.gap_readings(cmp["gaps"])}
    for mode in ("int8", "fp8"):
        out[mode] = judged(serve_driver.gap_readings(dense.served_logit_gaps(
            cmp["spec"], seed, cmp["seqs"], cmp["pad_to"],
            dtype=cmp["dtype"], mode=mode)), limits)
    return out


def control_train(run, seed: int, limits: dict) -> dict:
    cmp = run.compared
    ref = cmp["reference"]
    out = {}
    low = dense.train_reference(cmp["spec"], seed, cmp["batches"],
                                cmp["opt"], mode="bf16", dtype=cmp["dtype"])
    out["bf16"] = judged(train_driver.readings(low, ref), limits)
    half = [(t[: len(t) // 2], lab[: len(lab) // 2])
            for t, lab in cmp["batches"]]
    fault = dense.train_reference(cmp["spec"], seed, half, cmp["opt"],
                                  mode="f32", dtype=cmp["dtype"])
    out["half_batch"] = judged(train_driver.readings(fault, ref), limits)
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)

    c = bench_run.cell(args.workload)
    bench_run.device_info(c["workload"]["chips"])
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    control = (control_serve if c["config"]["path"] == "serve"
               else control_train)
    for seed in map(int, args.seeds.split(",")):
        run, setup_s = bench_run.execute(c, seed, args.seconds, False)
        line = {"seed": seed, "program": {k.name: k.value
                                          for k in run.checks},
                "setup_s": setup_s, "failed": run.failed,
                "notes": {k: v for k, v in run.notes.items()
                          if not isinstance(v, list)}}
        line["correct"] = all(k.ok for k in run.checks)
        if seed in controls:
            line["control"] = control(run, seed, c["config"]["limits"])
        print(json.dumps(line, default=float), flush=True)
        del run
    print(json.dumps({"done": True}), flush=True)


if __name__ == "__main__":
    main()
