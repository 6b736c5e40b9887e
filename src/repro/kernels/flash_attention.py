"""Causal / sliding-window GQA flash attention — Pallas TPU kernel.

Online-softmax blockwise attention (Rabe-Staats / FlashAttention) tiled for
VMEM: grid (batch, q_head, q_blocks, kv_blocks), with running max / sum /
accumulator scratch carried across the innermost (kv) grid dimension.
Irrelevant kv blocks (fully masked by causality or the sliding window) are
skipped via ``pl.when`` — on TPU the sequencer never issues their DMAs.

Forward only: the training path uses the differentiable chunked-jnp
implementation in ``repro.models.layers``; this kernel is the serving /
prefill fast path.  Validated in interpret mode against ``ref.attention_ref``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -1e30
_MINLANE = 128  # scratch minor dim (TPU lane width)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  block_q: int, block_k: int, n_k: int, seq_len: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    q_start = iq * block_q
    k_start = ik * block_k

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Block relevance: skip fully-masked kv blocks entirely.
    relevant = jnp.asarray(True)
    if causal:
        relevant &= k_start <= q_start + block_q - 1
    if window is not None:
        relevant &= k_start + block_k - 1 >= q_start - window + 1

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)            # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale

        rows = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 1)
        mask = cols < seq_len
        if causal:
            mask &= cols <= rows
        if window is not None:
            mask &= cols > rows - window
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, 0:1]                          # (bq, 1)
        l_prev = l_ref[:, 0:1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                          # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                 # (bq, 1)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * alpha
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == n_k - 1)
    def _finish():
        l = l_ref[:, 0:1]
        out = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = out.astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """q: (B, S, HQ, D); k, v: (B, S, HKV, D) -> (B, S, HQ, D)."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    block_q = min(block_q, s)
    block_k = min(block_k, s)

    # (B, H, S, D) layout; pad S to block multiples
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    pq = (-s) % block_q
    pk = (-s) % block_k
    if pq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pk), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pk), (0, 0)))
    sq = qt.shape[2]
    sk = kt.shape[2]
    grid = (b, hq, sq // block_q, sk // block_k)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_k=grid[3], seq_len=s)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bb, h, iq, ik: (bb, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bb, h, iq, ik, g=group: (bb, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bb, h, iq, ik, g=group: (bb, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bb, h, iq, ik: (bb, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _MINLANE), jnp.float32),
            pltpu.VMEM((block_q, _MINLANE), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return jnp.swapaxes(out[:, :, :s, :], 1, 2)
