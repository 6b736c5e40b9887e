"""Step builders: sharded train_step / prefill_step / serve_step.

These are what the dry-run lowers and what the trainer/server execute.
Everything is built from an ``ArchConfig`` + mesh + ``TrainConfig``; the
returned callables are ``jax.jit``s with explicit in/out shardings.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.dist import sharding as SH
from repro.models import transformer as T
from repro.optim.adam import Adam, AdamState, cosine_schedule


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10000
    weight_decay: float = 0.01
    grad_clip: float = 1.0
    grad_accum: int = 1            # microbatches (compute/comm overlap)
    moment_dtype: Optional[Any] = None  # e.g. jnp.bfloat16 halves opt memory
    seed: int = 0


def make_optimizer(tc: TrainConfig) -> Adam:
    return Adam(lr=cosine_schedule(tc.lr, tc.warmup_steps, tc.total_steps),
                weight_decay=tc.weight_decay,
                grad_clip_norm=tc.grad_clip,
                moment_dtype=tc.moment_dtype)


def _loss_for_grad(params, batch, cfg):
    loss, metrics = T.loss_fn(params, batch, cfg)
    return loss, metrics


def train_step_fn(cfg: T.ArchConfig, tc: TrainConfig
                  ) -> Callable[..., Tuple[Any, Any, Dict]]:
    """Returns f(params, opt_state, batch) -> (params, opt_state, metrics).

    grad_accum > 1 splits the batch into microbatches and accumulates via
    lax.scan — XLA overlaps the gradient all-reduce of microbatch i with the
    compute of microbatch i+1 (latency-hiding scheduler).
    """
    opt = make_optimizer(tc)

    def train_step(params, opt_state: AdamState, batch):
        if tc.grad_accum > 1:
            def micro(carry, mb):
                gacc, lacc = carry
                (loss, metrics), grads = jax.value_and_grad(
                    _loss_for_grad, has_aux=True)(params, mb, cfg)
                gacc = jax.tree.map(jnp.add, gacc, grads)
                return (gacc, lacc + loss), None

            mbs = jax.tree.map(
                lambda x: x.reshape(tc.grad_accum,
                                    x.shape[0] // tc.grad_accum,
                                    *x.shape[1:]), batch)
            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss), _ = jax.lax.scan(
                micro, (zeros, jnp.zeros((), jnp.float32)), mbs)
            grads = jax.tree.map(lambda g: g / tc.grad_accum, grads)
            loss = loss / tc.grad_accum
            metrics = {"nll": loss}
        else:
            (loss, metrics), grads = jax.value_and_grad(
                _loss_for_grad, has_aux=True)(params, batch, cfg)
        params, opt_state = opt.update(grads, opt_state, params)
        metrics = dict(metrics, loss=loss,
                       grad_norm=_global_norm(grads))
        return params, opt_state, metrics

    return train_step


def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in jax.tree.leaves(tree)))


def serve_step_fn(cfg: T.ArchConfig) -> Callable:
    """f(params, cache, tokens(B,1)) -> (logits (B,V), cache), and the MoE
    counters where ``cfg.counts_moe``."""

    def serve_decode(params, cache, tokens):
        return T.decode_step(params, cache, tokens, cfg)

    return serve_decode


def prefill_fn(cfg: T.ArchConfig, max_len: int) -> Callable:
    def serve_prefill(params, batch):
        return T.prefill(params, batch, cfg, max_len)

    return serve_prefill


# --------------------------------------------------------------------------
# Jitted, sharded builders
# --------------------------------------------------------------------------

def build_sharded_train_step(cfg: T.ArchConfig, tc: TrainConfig, mesh: Mesh,
                             rules: SH.ShardingRules = SH.ShardingRules(),
                             abstract_params=None):
    """jit(train_step) with explicit in/out shardings for (params, opt,
    batch). Returns (jitted_fn, state_shardings dict)."""
    if abstract_params is None:
        abstract_params = T.abstract_params(jax.random.PRNGKey(0), cfg)
    p_sh = SH.param_shardings(abstract_params, mesh, cfg, rules)
    opt = make_optimizer(tc)
    abstract_opt = jax.eval_shape(opt.init, abstract_params)
    o_sh = AdamState(step=NamedSharding(mesh, P()),
                     mu=p_sh, nu=p_sh)
    step = train_step_fn(cfg, tc)

    def batch_sh(batch_tree):
        return SH.batch_specs(batch_tree, mesh)

    def jitted(batch_abstract):
        b_sh = batch_sh(batch_abstract)
        b = jax.tree.leaves(batch_abstract)[0].shape[0]
        T.set_batch_axes(
            SH.fit_axes(b, SH.data_axes(mesh), mesh),
            seq_axis=rules.tp_axis if rules.sequence_parallel else None,
            seq_divisor=SH.axis_size(mesh, rules.tp_axis))
        return jax.jit(step,
                       in_shardings=(p_sh, o_sh, b_sh),
                       out_shardings=(p_sh, o_sh, None),
                       donate_argnums=(0, 1))

    return jitted, {"params": p_sh, "opt": o_sh}


def build_sharded_serve_step(cfg: T.ArchConfig, mesh: Mesh,
                             rules: SH.ShardingRules = SH.ShardingRules(),
                             abstract_params=None, abstract_cache=None,
                             batch: int = 1, max_len: int = 1024):
    if abstract_params is None:
        abstract_params = T.abstract_params(jax.random.PRNGKey(0), cfg)
    if abstract_cache is None:
        abstract_cache = jax.eval_shape(
            lambda: T.init_cache(cfg, batch, max_len))
    p_sh = SH.param_shardings(abstract_params, mesh, cfg, rules)
    c_sh = SH.cache_shardings(abstract_cache, mesh, cfg, rules)
    tok_sh = SH.batch_sharding(mesh, batch, 1)
    T.set_batch_axes(SH.fit_axes(batch, SH.data_axes(mesh), mesh))
    # (decode steps are seq-len 1 — SP constraint is a no-op there)
    step = serve_step_fn(cfg)
    jitted = jax.jit(step,
                     in_shardings=(p_sh, c_sh, tok_sh),
                     out_shardings=(None, c_sh) + (None,) * cfg.counts_moe,
                     donate_argnums=(1,))
    return jitted, {"params": p_sh, "cache": c_sh}


def build_sharded_prefill(cfg: T.ArchConfig, mesh: Mesh, max_len: int,
                          rules: SH.ShardingRules = SH.ShardingRules(),
                          abstract_params=None):
    if abstract_params is None:
        abstract_params = T.abstract_params(jax.random.PRNGKey(0), cfg)
    p_sh = SH.param_shardings(abstract_params, mesh, cfg, rules)
    step = prefill_fn(cfg, max_len)

    def jitted(batch_abstract):
        b_sh = SH.batch_specs(batch_abstract, mesh)
        b = jax.tree.leaves(batch_abstract)[0].shape[0]
        T.set_batch_axes(
            SH.fit_axes(b, SH.data_axes(mesh), mesh),
            seq_axis=rules.tp_axis if rules.sequence_parallel else None,
            seq_divisor=SH.axis_size(mesh, rules.tp_axis))
        return jax.jit(step, in_shardings=(p_sh, b_sh))

    return jitted, {"params": p_sh}
