"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --reduced --steps 200 --batch 8 --seq 256 --ckpt /tmp/ck

On a real pod, run one process per host with jax.distributed env vars; the
mesh helper then spans global devices and this same script drives the run
(single-controller-per-host SPMD).
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Optional, Sequence, Tuple

from repro.configs import ARCH_NAMES, get_config
from repro.data.pipeline import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.train.steps import TrainConfig
from repro.train.trainer import Trainer, TrainerConfig


def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="smollm-360m")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced smoke config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data", type=int, default=1, help="data mesh axis")
    ap.add_argument("--model", type=int, default=1, help="model mesh axis")
    return ap


def run(args) -> Tuple[Trainer, dict]:
    """Train ``--steps`` steps on a ``--data`` x ``--model`` mesh of the
    host's devices, resuming from the newest checkpoint under ``--ckpt``;
    returns the trainer and a summary (wall time includes compilation)."""
    cfg = get_config(args.arch, reduced=args.reduced)
    mesh = make_host_mesh(args.data, args.model)
    tc = TrainConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 1),
                     total_steps=args.steps, grad_accum=args.grad_accum)
    trc = TrainerConfig(steps=args.steps, ckpt_dir=args.ckpt,
                        ckpt_every=args.ckpt_every,
                        log_every=max(args.steps // 50, 1))
    dc = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                    global_batch=args.batch)
    trainer = Trainer(cfg, tc, trc, mesh, data_cfg=dc)

    from repro.models.transformer import param_count
    n = param_count(trainer.params)
    print(f"arch={cfg.name} params={n/1e6:.1f}M mesh={dict(mesh.shape)} "
          f"batch={args.batch}x{args.seq}", flush=True)
    t0 = time.time()
    log = trainer.run()
    dt = time.time() - t0
    losses = [e for e in log if "loss" in e]
    return trainer, {"first_loss": losses[0]["loss"],
                     "last_loss": losses[-1]["loss"],
                     "steps": trainer.step,
                     "wall_s": round(dt, 1),
                     "tokens_per_s": round(
                         trainer.step * args.batch * args.seq / dt)}


def main(argv: Optional[Sequence[str]] = None):
    args = make_parser().parse_args(argv)
    enable_compile_cache()
    print(json.dumps(run(args)[1], indent=1))


if __name__ == "__main__":
    main()
