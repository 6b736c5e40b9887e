"""Core transformer building blocks (pure-jnp, GSPMD-friendly).

All functions are shape-polymorphic over leading batch dims and written so
the 512-device dry-run lowers to small HLO:

  * attention is KV-chunked (online softmax) — memory O(S * chunk), never
    O(S^2), differentiable through ``lax.scan``;
  * decode attends against a KV cache with sequence sharding in mind: the
    softmax reductions over the (sharded) cache dimension lower to partial
    reductions + small all-reduces (flash-decoding semantics via GSPMD);
  * every projection is an einsum so GSPMD can propagate shardings.

Parameters are plain nested dicts; init helpers return matching pytrees and
are always invoked under ``jax.eval_shape`` by the dry-run (no allocation).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


# ---------------------------------------------------------------- basic ops

def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * w


def dense(x: jnp.ndarray, w: jnp.ndarray,
          b: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    out = jnp.einsum("...d,df->...f", x, w.astype(x.dtype))
    if b is not None:
        out = out + b.astype(x.dtype)
    return out


def rope(x: jnp.ndarray, positions: jnp.ndarray,
         theta: float = 10000.0) -> jnp.ndarray:
    """Rotary embedding. x: (..., S, H, D), positions: (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :].astype(x.dtype)
    sin = jnp.sin(ang)[..., None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def sinusoidal_positions(s: int, d: int, dtype=jnp.float32) -> jnp.ndarray:
    pos = jnp.arange(s)[:, None].astype(jnp.float32)
    div = jnp.exp(jnp.arange(0, d, 2) * (-math.log(10000.0) / d))
    pe = jnp.zeros((s, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe.astype(dtype)


# ------------------------------------------------------------- init helpers

def _winit(rng, shape, fan_in, dtype):
    return (jax.random.normal(rng, shape, jnp.float32)
            * (1.0 / math.sqrt(fan_in))).astype(dtype)


def init_attention(rng, d_model: int, n_heads: int, n_kv_heads: int,
                   head_dim: int, qkv_bias: bool, dtype) -> Params:
    rs = jax.random.split(rng, 5)
    p = {
        "ln": jnp.ones((d_model,), dtype),
        "wq": _winit(rs[0], (d_model, n_heads * head_dim), d_model, dtype),
        "wk": _winit(rs[1], (d_model, n_kv_heads * head_dim), d_model, dtype),
        "wv": _winit(rs[2], (d_model, n_kv_heads * head_dim), d_model, dtype),
        "wo": _winit(rs[3], (n_heads * head_dim, d_model),
                     n_heads * head_dim, dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
        p["bv"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
    return p


def init_mlp(rng, d_model: int, d_ff: int, variant: str, dtype) -> Params:
    rs = jax.random.split(rng, 3)
    if variant == "swiglu":
        return {"ln": jnp.ones((d_model,), dtype),
                "w_gate": _winit(rs[0], (d_model, d_ff), d_model, dtype),
                "w_up": _winit(rs[1], (d_model, d_ff), d_model, dtype),
                "w_down": _winit(rs[2], (d_ff, d_model), d_ff, dtype)}
    return {"ln": jnp.ones((d_model,), dtype),  # gelu (whisper-style)
            "w_in": _winit(rs[0], (d_model, d_ff), d_model, dtype),
            "b_in": jnp.zeros((d_ff,), dtype),
            "w_out": _winit(rs[1], (d_ff, d_model), d_ff, dtype),
            "b_out": jnp.zeros((d_model,), dtype)}


def mlp(x: jnp.ndarray, p: Params, variant: str = "swiglu",
        eps: float = 1e-6) -> jnp.ndarray:
    h = rmsnorm(x, p["ln"], eps)
    if variant == "swiglu":
        g = jax.nn.silu(dense(h, p["w_gate"]))
        u = dense(h, p["w_up"])
        return x + dense(g * u, p["w_down"])
    h = jax.nn.gelu(dense(h, p["w_in"], p["b_in"]))
    return x + dense(h, p["w_out"], p["b_out"])


# -------------------------------------------------------- chunked attention
#
# Flash-style attention with a *manual* backward (custom_vjp).  Naive scan
# autodiff would save the per-chunk probabilities -> O(S^2) residuals, which
# is exactly what flash attention exists to avoid.  Forward saves only
# (q, k, v, out, logsumexp) = O(S); backward re-scans over kv chunks
# recomputing probabilities from the saved logsumexp.

def _mask_for(ci, chunk, rows, sk, causal, window):
    cols = ci * chunk + jnp.arange(chunk)
    mask = cols[None, :] < sk
    if causal:
        mask &= cols[None, :] <= rows[:, None]
    if window is not None:
        mask &= cols[None, :] > rows[:, None] - window
    return mask  # (Sq, chunk)


def _chunked_attn_fwd_impl(q, k, v, causal, window, chunk, q_offset):
    """Returns (out (B,Sq,HQ,Dv), lse (B,KV,G,Sq))."""
    b, sq, hq, d = q.shape
    sk, hkv, dv_w = k.shape[1], k.shape[2], v.shape[-1]
    group = hq // hkv
    chunk = min(chunk, sk)
    n_chunks = -(-sk // chunk)
    pad = n_chunks * chunk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    scale = 1.0 / math.sqrt(d)
    qg = (q.astype(jnp.float32) * scale).reshape(b, sq, hkv, group, d)
    kc = k.reshape(b, n_chunks, chunk, hkv, d)
    vc = v.reshape(b, n_chunks, chunk, hkv, dv_w)
    rows = q_offset + jnp.arange(sq)

    def step(carry, inp):
        m_prev, l_prev, acc = carry
        kci, vci, ci = inp
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kci.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        mask = _mask_for(ci, chunk, rows, sk, causal, window)
        s = jnp.where(mask, s, -1e30)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        acc = (acc * alpha[..., None]
               + jnp.einsum("bhgqk,bkhd->bhgqd", p,
                            vci.astype(jnp.float32),
                            preferred_element_type=jnp.float32))
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, hkv, group, sq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, hkv, group, sq), jnp.float32)
    acc0 = jnp.zeros((b, hkv, group, sq, dv_w), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, acc0),
        (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0),
         jnp.arange(n_chunks)))
    lsafe = jnp.where(l == 0, 1.0, l)
    out = acc / lsafe[..., None]
    lse = m + jnp.log(lsafe)
    out = jnp.moveaxis(out, 3, 1).reshape(b, sq, hq, dv_w).astype(q.dtype)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      causal: bool = True, window: Optional[int] = None,
                      chunk: int = 1024,
                      q_offset: int = 0) -> jnp.ndarray:
    """Online-softmax attention, KV-chunked. q, k: (B,Sq|Sk,H|KV,D), v:
    (B,Sk,KV,Dv); the output is (B,Sq,H,Dv), scaled by 1/sqrt(D).

    Memory O(Sq * chunk) in both passes. ``q_offset``: absolute position of
    q[0] (prefill continuation)."""
    out, _ = _chunked_attn_fwd_impl(q, k, v, causal, window, chunk, q_offset)
    return out


def _chunked_attn_fwd(q, k, v, causal, window, chunk, q_offset):
    out, lse = _chunked_attn_fwd_impl(q, k, v, causal, window, chunk,
                                      q_offset)
    return out, (q, k, v, out, lse)


def _chunked_attn_bwd(causal, window, chunk, q_offset, res, dout):
    q, k, v, out, lse = res
    b, sq, hq, d = q.shape
    sk, hkv, dv_w = k.shape[1], k.shape[2], v.shape[-1]
    group = hq // hkv
    chunk = min(chunk, sk)
    n_chunks = -(-sk // chunk)
    pad = n_chunks * chunk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    scale = 1.0 / math.sqrt(d)
    qg = (q.astype(jnp.float32) * scale).reshape(b, sq, hkv, group, d)
    dog = dout.astype(jnp.float32).reshape(b, sq, hkv, group, dv_w)
    og = out.astype(jnp.float32).reshape(b, sq, hkv, group, dv_w)
    dog = jnp.moveaxis(dog, 1, 3)   # (B,KV,G,Sq,D)
    og = jnp.moveaxis(og, 1, 3)
    delta = jnp.sum(dog * og, axis=-1)            # (B,KV,G,Sq)
    kc = jnp.moveaxis(k.reshape(b, n_chunks, chunk, hkv, d), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, n_chunks, chunk, hkv, dv_w), 1, 0)
    rows = q_offset + jnp.arange(sq)

    def step(dq, inp):
        kci, vci, ci = inp
        kf = kci.astype(jnp.float32)
        vf = vci.astype(jnp.float32)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kf,
                       preferred_element_type=jnp.float32)
        mask = _mask_for(ci, chunk, rows, sk, causal, window)
        s = jnp.where(mask, s, -1e30)
        p = jnp.where(mask, jnp.exp(s - lse[..., None]), 0.0)
        dv_c = jnp.einsum("bhgqk,bhgqd->bkhd", p, dog)
        dp = jnp.einsum("bhgqd,bkhd->bhgqk", dog, vf)
        ds = p * (dp - delta[..., None])
        dq = dq + jnp.einsum("bhgqk,bkhd->bqhgd", ds, kf) * scale
        dk_c = jnp.einsum("bhgqk,bqhgd->bkhd", ds, qg)  # qg carries scale
        return dq, (dk_c, dv_c)

    dq0 = jnp.zeros((b, sq, hkv, group, d), jnp.float32)
    dq, (dk_c, dv_c) = jax.lax.scan(
        step, dq0, (kc, vc, jnp.arange(n_chunks)))
    dk = jnp.moveaxis(dk_c, 0, 1).reshape(b, n_chunks * chunk, hkv, d)
    dv = jnp.moveaxis(dv_c, 0, 1).reshape(b, n_chunks * chunk, hkv, dv_w)
    dq = dq.reshape(b, sq, hq, d).astype(q.dtype)
    return dq, dk[:, :sk].astype(k.dtype), dv[:, :sk].astype(v.dtype)


chunked_attention.defvjp(_chunked_attn_fwd, _chunked_attn_bwd)


def attention_block(x: jnp.ndarray, p: Params, cfg, positions: jnp.ndarray,
                    causal: bool = True,
                    window: Optional[int] = None,
                    cross_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
                    ) -> jnp.ndarray:
    """Full attention block (prefill/train path). x: (B, S, D_model)."""
    b, s, _ = x.shape
    h = rmsnorm(x, p["ln"])
    q = dense(h, p["wq"], p.get("bq")).reshape(b, s, cfg.n_heads, cfg.head_dim)
    if cross_kv is None:
        k = dense(h, p["wk"], p.get("bk")).reshape(b, s, cfg.n_kv_heads,
                                                   cfg.head_dim)
        v = dense(h, p["wv"], p.get("bv")).reshape(b, s, cfg.n_kv_heads,
                                                   cfg.head_dim)
        if cfg.use_rope:
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
    else:
        k, v = cross_kv
    out = chunked_attention(q, k, v, causal=causal, window=window,
                            chunk=cfg.attn_chunk)
    out = out.reshape(b, s, cfg.n_heads * cfg.head_dim)
    return x + dense(out, p["wo"])


# ------------------------------------------------------------ decode (KV$)

def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, cache_len: jnp.ndarray,
                     window: Optional[int] = None) -> jnp.ndarray:
    """One-token attention against a cache.

    q: (B, 1, HQ, D); caches: (B, S_max, HKV, D); cache_len: () or (B,).
    The reduction over S_max is GSPMD-shardable (sequence-parallel decode).
    """
    b, _, hq, d = q.shape
    smax, hkv = k_cache.shape[1], k_cache.shape[2]
    group = hq // hkv
    scale = 1.0 / math.sqrt(d)
    qg = (q * scale).reshape(b, hkv, group, d)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache,
                   preferred_element_type=jnp.float32)
    idx = jnp.arange(smax)
    length = jnp.broadcast_to(jnp.asarray(cache_len), (b,))
    mask = idx[None, :] < length[:, None]
    if window is not None:
        mask &= idx[None, :] >= jnp.maximum(length[:, None] - window, 0)
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhgk,bkhd->bhgd", (p / l).astype(v_cache.dtype),
                     v_cache, preferred_element_type=jnp.float32)
    return out.reshape(b, 1, hq, d).astype(q.dtype)


def update_kv_cache(k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                    k_new: jnp.ndarray, v_new: jnp.ndarray,
                    layer: jnp.ndarray, position: jnp.ndarray,
                    ring: bool = False
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Write one token per sequence into layer ``layer`` of stacked caches.

    caches: (R, B, S_max, HKV, D); new: (B, 1, HKV, D); ``position``: (B,),
    so each slot in the batch can be at a different decode depth
    (continuous batching).  ``ring``: modulo wraparound (sliding-window
    caches store only the last ``S_max`` tokens).  One scatter per cache at
    ``(layer, b, position[b])``, which XLA runs in place on a donated or
    loop-carried cache; a position past ``S_max`` writes nothing.
    """
    b, smax = k_cache.shape[1], k_cache.shape[2]
    pos = position % smax if ring else position
    at = (layer, jnp.arange(b), pos)
    return k_cache.at[at].set(k_new[:, 0]), v_cache.at[at].set(v_new[:, 0])


def decode_attention_ring(q, k_cache, v_cache, position,
                          window: int) -> jnp.ndarray:
    """Decode against a ring-buffer window cache (mixtral SWA long-decode).

    The cache holds the last ``S_max`` = window tokens; all valid once full.
    """
    smax = k_cache.shape[1]
    filled = jnp.minimum(jnp.asarray(position) + 1, smax)
    return decode_attention(q, k_cache, v_cache, filled, window=None)


# ------------------------------------------------ multi-head latent attention
#
# DeepSeek-V2/V3 MLA without a query latent: per head a query of
# ``nope + rope`` dims; one ``kv_lora_rank``-wide latent ``c`` per position
# (RMS-normed) from which each head's key-nope and value are expanded, and
# one ``rope``-wide key shared by all heads. The decode cache holds only
# ``c`` and the roped shared key.

def init_mla(rng, d_model: int, n_heads: int, kv_lora: int, nope: int,
             rope_dim: int, v_dim: int, dtype) -> Params:
    rs = jax.random.split(rng, 4)
    return {
        "ln": jnp.ones((d_model,), dtype),
        "wq": _winit(rs[0], (d_model, n_heads * (nope + rope_dim)), d_model,
                     dtype),
        "wkv_a": _winit(rs[1], (d_model, kv_lora + rope_dim), d_model,
                        dtype),
        "kv_ln": jnp.ones((kv_lora,), dtype),
        "wkv_b": _winit(rs[2], (kv_lora, n_heads * (nope + v_dim)), kv_lora,
                        dtype),
        "wo": _winit(rs[3], (n_heads * v_dim, d_model), n_heads * v_dim,
                     dtype),
    }


def mla_project(h: jnp.ndarray, p: Params, cfg, positions: jnp.ndarray):
    """The normed input h (B, S, D) -> roped queries (B, S, H, nope+rope),
    the normed latent c (B, S, kv_lora) and the roped shared key
    (B, S, rope)."""
    b, s, _ = h.shape
    nope, r, lora = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
        cfg.kv_lora_rank
    q = dense(h, p["wq"]).reshape(b, s, cfg.n_heads, nope + r)
    q = jnp.concatenate(
        [q[..., :nope], rope(q[..., nope:], positions, cfg.rope_theta)], -1)
    kva = dense(h, p["wkv_a"])
    c = rmsnorm(kva[..., :lora], p["kv_ln"], cfg.rms_eps)
    k_pe = rope(kva[..., None, lora:], positions, cfg.rope_theta)[..., 0, :]
    return q, c, k_pe


def mla_expand(c: jnp.ndarray, k_pe: jnp.ndarray, p: Params, cfg):
    """Per-head keys (B, S, H, nope+rope) and values (B, S, H, v) from the
    latent and the shared roped key (prefill and training)."""
    b, s, _ = c.shape
    nope, h = cfg.qk_nope_head_dim, cfg.n_heads
    kv = dense(c, p["wkv_b"]).reshape(b, s, h, nope + cfg.v_head_dim)
    k_pe = jnp.broadcast_to(k_pe[:, :, None, :], (b, s, h, k_pe.shape[-1]))
    return jnp.concatenate([kv[..., :nope], k_pe], -1), kv[..., nope:]


def mla_decode_attention(q: jnp.ndarray, c_cache: jnp.ndarray,
                         kpe_cache: jnp.ndarray, wkv_b: jnp.ndarray,
                         cache_len: jnp.ndarray, cfg) -> jnp.ndarray:
    """One-token MLA against the latent cache, with the key and value
    expansions absorbed: each head's nope query goes through its key
    expansion into the latent's width, the scores read the latent and the
    shared key once for all heads, and the weighted latent goes through the
    value expansion. q: (B, 1, H, nope+rope); caches (B, S, kv_lora) and
    (B, S, rope); returns (B, 1, H, v)."""
    b, _, h, dq = q.shape
    nope, lora = cfg.qk_nope_head_dim, c_cache.shape[-1]
    w = wkv_b.astype(q.dtype).reshape(lora, h, nope + cfg.v_head_dim)
    q_lat = jnp.einsum("bhn,chn->bhc", q[:, 0, :, :nope], w[..., :nope],
                       preferred_element_type=jnp.float32).astype(q.dtype)
    s = (jnp.einsum("bhc,bkc->bhk", q_lat, c_cache,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhr,bkr->bhk", q[:, 0, :, nope:], kpe_cache,
                      preferred_element_type=jnp.float32)) / math.sqrt(dq)
    length = jnp.broadcast_to(jnp.asarray(cache_len), (b,))
    mask = jnp.arange(c_cache.shape[1])[None, :] < length[:, None]
    s = jnp.where(mask[:, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhk,bkc->bhc", p.astype(c_cache.dtype), c_cache,
                   preferred_element_type=jnp.float32).astype(q.dtype)
    out = jnp.einsum("bhc,chv->bhv", o, w[..., nope:],
                     preferred_element_type=jnp.float32)
    return out[:, None].astype(q.dtype)
