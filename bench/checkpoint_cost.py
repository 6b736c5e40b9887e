"""What the train driver's checkpoint stand-in leaves out, timed on the
chip:

    python3 bench/checkpoint_cost.py --workload smollm-360m.train --saves 2

Builds the cell's ``Trainer`` as the driver does, with the program's own
checkpoint manager in a temporary directory, and times ``--saves``
synchronous saves of its whole state, the call ``Trainer.run`` makes at its
start and at its end. One JSON line gives the seconds of each save and the
bytes it wrote; the directory is removed. The benchmark's own runs never
run this.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402

from bench import program, run as bench_run  # noqa: E402
from repro.data.pipeline import DataConfig  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.train.steps import TrainConfig  # noqa: E402
from repro.train.trainer import Trainer, TrainerConfig  # noqa: E402


def _bytes(path: str) -> int:
    return sum(os.path.getsize(os.path.join(d, f))
               for d, _, files in os.walk(path) for f in files)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--saves", type=int, default=2)
    ap.add_argument("--reduced", action="store_true",
                    help="the program's small variant (CPU rehearsal)")
    args = ap.parse_args(argv)

    c = bench_run.cell(args.workload)
    if not args.reduced:
        bench_run.device_info(c["workload"]["chips"])
    conf, geo = c["config"], c["config"]["train"]
    cfg = program.arch_config(conf, reduced=args.reduced)
    ck = tempfile.mkdtemp(prefix="bench_ckpt_cost_")
    try:
        trainer = Trainer(
            cfg, TrainConfig(), TrainerConfig(steps=0, ckpt_dir=ck, keep=1),
            make_host_mesh(1, 1),
            data_cfg=DataConfig(vocab=cfg.vocab, seq_len=geo["seq"],
                                global_batch=geo["batch"]))
        jax.block_until_ready((trainer.params, trainer.opt_state))
        secs, sizes = [], []
        for step in range(args.saves):
            t = time.perf_counter()
            trainer.ckpt.save_sync(step, {"params": trainer.params,
                                          "opt": trainer.opt_state},
                                   {"data_step": step})
            secs.append(time.perf_counter() - t)
            sizes.append(_bytes(ck))
    finally:
        shutil.rmtree(ck, ignore_errors=True)
    print(json.dumps({"workload": args.workload, "save_s": secs,
                      "bytes_on_disk": sizes}), flush=True)


if __name__ == "__main__":
    main()
