"""Train path: the program's ``Trainer`` (``repro.train.trainer``) on a 1x1
mesh, built as ``repro.launch.train`` builds it, fed by the program's own
``SyntheticLM`` and ``Prefetcher`` seeded from ``--seed``.

Set-up builds one trainer, gives it the seeded weights and fresh AdamW
moments, and runs its first ``FIRST_STEPS`` steps through ``Trainer.run``;
those steps compile the step program and are the ones the reference
follows, on the very batches the trainer was handed. The same trainer then
runs the window: a second ``Trainer.run`` whose loop goes on until the
window's time is up.

The trainer's per-step ``injector`` (``maybe_fail(step, batch)``, called
before every step) is the clock and the tap on the data: it keeps the
first ``FIRST_STEPS`` batches, and the window runs from its call before
the first window step to its first call at or after the close, so it holds
whole steps and every host gap between them. At that call the loop bound
is lowered so the step that is already under way is the last.

The trainer's own checkpoints are replaced by a stand-in that writes
nothing: two synchronous saves of the whole state per ``Trainer.run``
would write several GB to the machine's disk on every run of every check.
``bench/checkpoint_cost.py`` times what one save would add.
"""
from __future__ import annotations

import shutil
import tempfile
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import program
from bench.common import Check, Run, SubTrace, memory_peak_bytes
from bench.reference import dense
from repro.data.pipeline import DataConfig
from repro.launch.mesh import make_host_mesh
from repro.train.steps import TrainConfig, make_optimizer
from repro.train.trainer import Trainer, TrainerConfig

FIRST_STEPS = 3   # steps in set-up that the reference follows
B1 = 0.9          # AdamW's first-moment decay (the program's default)


class NoCheckpoints:
    """Stands in for the trainer's checkpoint manager; writes nothing."""

    def __init__(self):
        self.saves = 0

    def save_sync(self, *_, **__):
        self.saves += 1

    save_async = save_sync

    def wait(self):
        pass

    def latest_step(self) -> Optional[int]:
        return None


class Clock:
    """The trainer's injector: keeps copies of the first ``FIRST_STEPS``
    batches it is handed, runs a hook before a given step and, once
    ``seconds`` is set, opens the window at its next call, stamps each step,
    and closes the window at the first call ``seconds`` after it opened."""

    def __init__(self):
        self.trainer: Optional[Trainer] = None
        self.hooks: Dict[int, callable] = {}
        self.batches = []
        self.stamps = []
        self.seconds: Optional[float] = None
        self.tracer: Optional[SubTrace] = None
        self._span = None

    def maybe_fail(self, step: int, batch):
        now = time.perf_counter()
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        if step in self.hooks:
            self.hooks.pop(step)()
        if len(self.batches) < FIRST_STEPS:
            self.batches.append((np.array(batch["tokens"]),
                                 np.array(batch["labels"])))
        if self.seconds is not None:
            self.stamps.append((step, now))
            self.tracer.poll(now - self.stamps[0][1])
            if now >= self.stamps[0][1] + self.seconds:
                self.trainer.trc.steps = step
                self.seconds = None
                return batch
            self._span = jax.profiler.TraceAnnotation("bench.train.step")
            self._span.__enter__()
        return batch


def _opt(geo: dict) -> dense.AdamW:
    return dense.AdamW(lr=geo["lr"], warmup=geo["warmup_steps"],
                       total=geo["total_steps"],
                       weight_decay=geo["weight_decay"],
                       clip_norm=geo["grad_clip"])


def _worst_gap(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
               keep: Dict[str, np.ndarray]) -> float:
    """Largest |program norm - reference norm| over leaves, each against the
    larger of its reference norm and the median leaf's."""
    r = np.concatenate([ref[k][keep[k]] for k in ref])
    p = np.concatenate([prog[k][keep[k]] for k in ref])
    floor = np.median(r)
    return float(np.max(np.abs(p - r) / np.maximum(r, floor)))


def run(ctx) -> Run:
    c, seed, seconds = ctx.config, ctx.seed, ctx.seconds
    geo = c["train"]
    cfg = program.arch_config(c, reduced=ctx.reduced)
    tc = TrainConfig(lr=geo["lr"], warmup_steps=geo["warmup_steps"],
                     total_steps=geo["total_steps"],
                     weight_decay=geo["weight_decay"],
                     grad_clip=geo["grad_clip"])
    clock = Clock()
    ck = tempfile.mkdtemp(prefix="bench_ck_")
    t = time.perf_counter()
    trainer = Trainer(
        cfg, tc, TrainerConfig(steps=FIRST_STEPS, ckpt_dir=ck,
                               ckpt_every=10 ** 9, log_every=1),
        make_host_mesh(1, 1),
        data_cfg=DataConfig(vocab=cfg.vocab, seq_len=geo["seq"],
                            global_batch=geo["batch"],
                            seed=int(seed) & (2 ** 64 - 1)),
        injector=clock)
    trainer_init_s = time.perf_counter() - t
    clock.trainer = trainer
    shutil.rmtree(ck, ignore_errors=True)
    trainer.ckpt = NoCheckpoints()
    # the program's own initial state goes; the seeded one takes its place
    for a in jax.tree.leaves((trainer.params, trainer.opt_state)):
        a.delete()
    trainer.params = jax.device_put(program.make_params(cfg, seed),
                                    trainer.p_sh)
    trainer.opt_state = jax.device_put(
        make_optimizer(tc).init(trainer.params), trainer.o_sh)
    seen = {}

    def first_grad():      # before step 1: AdamW's state after step 0
        mu = program.to_reference(trainer.opt_state.mu)
        seen["grad"] = dense.leaf_norms(
            jax.tree.map(lambda m: m.astype(jnp.float32) / (1 - B1), mu))

    clock.hooks[1] = first_grad
    trainer.run()
    p0 = program.make_params(cfg, seed)      # made again: no copy in steps
    change = dense.leaf_norms(program.to_reference(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        trainer.params, p0)))
    for a in jax.tree.leaves(p0):
        a.delete()
    losses = [e["loss"] for e in trainer.metrics_log if "loss" in e]

    # ---- the window: the same trainer, on until the time is up
    run = Run()
    trainer.trc.steps = 10 ** 9
    compiles0 = ctx.compile_log.n
    clock.tracer = SubTrace(ctx.trace, run, seconds)
    ctx.open_window()
    clock.seconds = seconds
    trainer.run()
    clock.tracer.finish()
    compiles = ctx.compile_log.n - compiles0
    run.memory_peak_bytes = memory_peak_bytes()
    (s0, t0), (s1, t1) = clock.stamps[0], clock.stamps[-1]
    tokens = (s1 - s0) * geo["batch"] * geo["seq"]
    run.window_s = t1 - t0
    run.metrics = {"train_tokens_per_s": tokens / (t1 - t0)}
    run.steps = [{"step": s, "t": t - t0} for s, t in clock.stamps]
    run.attempted = run.notes["window_steps"] = s1 - s0
    run.failed = trainer.restarts
    run.notes.update({"window_compiles": compiles,
                      "checkpoint_saves_skipped": trainer.ckpt.saves,
                      "trainer_init_s": trainer_init_s})

    # ---- correctness: free the trainer's state, then the reference
    for a in jax.tree.leaves((trainer.params, trainer.opt_state)):
        a.delete()
    batches = clock.batches
    rows = [r.tobytes() for t, _ in batches for r in t]
    run.compared = {"spec": program.spec_of(cfg), "batches": batches,
                    "opt": _opt(geo), "dtype": cfg.param_dtype}
    t_ref = time.perf_counter()
    ref_losses, ref_grad, ref_change = dense.train_reference(
        program.spec_of(cfg), seed, batches, _opt(geo), mode="f32",
        dtype=cfg.param_dtype)
    run.notes["reference_s"] = time.perf_counter() - t_ref
    run.compared["reference"] = (ref_losses, ref_grad, ref_change)
    lim = run.compared["limits"] = c["limits"]
    got = readings((losses, seen["grad"], change),
                   (ref_losses, ref_grad, ref_change))
    run.checks = [Check("window_compiles", compiles, 0),
                  Check("rows_repeated", len(rows) - len(set(rows)), 0)] + [
        Check(k, got[k], lim[k]) for k in sorted(lim)]
    run.notes["not_compared"] = {k: v for k, v in got.items()
                                 if k not in lim}
    run.notes["losses"] = losses
    run.notes["reference_losses"] = ref_losses
    return run


def readings(got, ref) -> Dict[str, float]:
    """The three numbers compared: the worst relative loss gap over the
    steps, and the worst leaf's gap of first-gradient and of change norms
    (leaves chosen by :func:`leaves_that_move`)."""
    (losses, grad, change), (ref_losses, ref_grad, ref_change) = got, ref
    keep = leaves_that_move(ref_grad)
    return {"loss_rel_gap": max(abs(a - b) / abs(b)
                                for a, b in zip(losses, ref_losses)),
            "grad_norm_gap": _worst_gap(grad, ref_grad, keep),
            "update_norm_gap": _worst_gap(change, ref_change, keep)}


def leaves_that_move(ref_grad: Dict[str, np.ndarray]
                     ) -> Dict[str, np.ndarray]:
    """Leaves compared: those whose reference gradient norm is at least a
    thousandth of the median leaf's. The others move under AdamW by
    round-off alone."""
    med = np.median(np.concatenate(list(ref_grad.values())))
    return {k: v >= 1e-3 * med for k, v in ref_grad.items()}
