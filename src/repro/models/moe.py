"""Mixture-of-Experts layer (token-choice top-k router).

Three execution strategies, selected by ``cfg.moe_impl``:

* ``dense``    — every expert computes every token, router probs zero out the
                 unselected ones.  Exact top-k math, O(E/k) extra FLOPs;
                 used by reduced smoke tests (tiny E).
* ``dropping`` — capacity-based dispatch in token groups (the standard GSPMD
                 MoE): one-hot combine/dispatch einsums sized
                 (groups, group_tokens, E, capacity).  Expert weights carry
                 the expert dim so the sharding rules can lay experts across
                 the ``model`` axis (EP) or shard d_ff instead (TP fallback
                 when E doesn't divide the axis).
* ``dropless`` — the (token, expert) pairs that fall on the experts this
                 chip holds are sorted by expert and run through grouped
                 products (the megablox ``gmm`` kernel); no token is
                 dropped. Below one row tile of tokens (decode) every held
                 expert runs on every token instead, masked by the
                 routing: ``gmm`` would pad each group to a tile anyway.
                 The layer holds ``cfg.held_experts`` experts from id
                 ``cfg.first_expert`` on, routes over all
                 ``cfg.n_experts`` by sigmoid scores with a selection bias
                 (:func:`route`) and computes only its own experts' part
                 of the result (expert parallelism without its exchange).
                 Shared experts, applied to every token, may sit beside
                 the routed ones. It also returns its counters
                 (:func:`moe_dropless`).

Aux: load-balancing loss (Switch-style) is returned for the trainer.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from jax.experimental.pallas.ops.tpu.megablox import gmm

from repro.kernels import interpret_mode
from repro.models.layers import Params, _winit, dense, rmsnorm


def init_moe(rng, d_model: int, d_ff: int, n_experts: int, dtype, *,
             n_router: int = 0, n_shared: int = 0,
             router_bias: bool = False) -> Params:
    """``n_experts`` held experts of width ``d_ff``, a router over
    ``n_router`` (default: the held ones), an optional selection bias, and
    ``n_shared`` shared experts as one SwiGLU of ``n_shared * d_ff``."""
    rs = jax.random.split(rng, 4)
    n_router = n_router or n_experts
    p = {
        "ln": jnp.ones((d_model,), dtype),
        "router": _winit(rs[0], (d_model, n_router), d_model, jnp.float32),
        "w_gate": _winit(rs[1], (n_experts, d_model, d_ff), d_model, dtype),
        "w_up": _winit(rs[2], (n_experts, d_model, d_ff), d_model, dtype),
        "w_down": _winit(rs[3], (n_experts, d_ff, d_model), d_ff, dtype),
    }
    if router_bias:
        p["router_bias"] = jnp.zeros((n_router,), jnp.float32)
    if n_shared:
        ks = jax.random.split(jax.random.fold_in(rng, 4), 3)
        f = n_shared * d_ff
        p["shared"] = {"w_gate": _winit(ks[0], (d_model, f), d_model, dtype),
                       "w_up": _winit(ks[1], (d_model, f), d_model, dtype),
                       "w_down": _winit(ks[2], (f, d_model), f, dtype)}
    return p


def _router(h: jnp.ndarray, p: Params, top_k: int
            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (probs (..., E) with only top-k nonzero, idx (..., k), aux)."""
    logits = jnp.einsum("...d,de->...e", h.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    mask = jax.nn.one_hot(top_i, logits.shape[-1], dtype=probs.dtype)
    sparse_p = jnp.einsum("...ke,...k->...e", mask, top_p)
    # Switch load-balance loss: E * sum_e f_e * p_e
    e = logits.shape[-1]
    f = jnp.mean(mask.sum(-2).reshape(-1, e), axis=0)  # fraction routed
    pbar = jnp.mean(probs.reshape(-1, e), axis=0)
    aux = e * jnp.sum(f * pbar)
    return sparse_p, top_i, aux


def moe_dense(x: jnp.ndarray, p: Params, top_k: int
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    h = rmsnorm(x, p["ln"])
    sparse_p, _, aux = _router(h, p, top_k)
    g = jax.nn.silu(jnp.einsum("...d,edf->...ef", h, p["w_gate"].astype(x.dtype)))
    u = jnp.einsum("...d,edf->...ef", h, p["w_up"].astype(x.dtype))
    y = jnp.einsum("...ef,efd->...ed", g * u, p["w_down"].astype(x.dtype))
    out = jnp.einsum("...ed,...e->...d", y, sparse_p.astype(x.dtype))
    return x + out, aux


def moe_dropping(x: jnp.ndarray, p: Params, top_k: int,
                 capacity_factor: float = 1.25,
                 group_size: int = 2048) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Capacity-based dispatch (GSPMD MoE). x: (B, S, D)."""
    b, s, d = x.shape
    e = p["router"].shape[-1]
    h = rmsnorm(x, p["ln"])
    tokens = h.reshape(-1, d)
    n = tokens.shape[0]
    g_sz = min(group_size, n)
    n_groups = -(-n // g_sz)
    pad = n_groups * g_sz - n
    if pad:
        tokens = jnp.pad(tokens, ((0, pad), (0, 0)))
    grp = tokens.reshape(n_groups, g_sz, d)

    sparse_p, top_i, aux = _router(grp, p, top_k)          # (G, T, E)
    cap = max(int(g_sz * top_k / e * capacity_factor), 4)

    # position of each token within its expert's capacity buffer
    expert_mask = jax.nn.one_hot(top_i, e, dtype=jnp.int32)   # (G,T,k,E)
    pos_in_expert = (jnp.cumsum(expert_mask.sum(2), axis=1)
                     - expert_mask.sum(2))                    # (G,T,E)
    keep = pos_in_expert < cap
    disp = (jax.nn.one_hot(pos_in_expert, cap, dtype=x.dtype)
            * (expert_mask.sum(2) * keep)[..., None].astype(x.dtype))
    # disp: (G, T, E, C) 0/1 dispatch tensor
    comb = disp * sparse_p[..., None].astype(x.dtype)         # weighted

    xin = jnp.einsum("gtec,gtd->gecd", disp, grp)             # (G,E,C,D)
    gact = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xin,
                                  p["w_gate"].astype(x.dtype)))
    uact = jnp.einsum("gecd,edf->gecf", xin, p["w_up"].astype(x.dtype))
    yout = jnp.einsum("gecf,efd->gecd", gact * uact,
                      p["w_down"].astype(x.dtype))
    out = jnp.einsum("gtec,gecd->gtd", comb, yout)            # (G,T,D)
    out = out.reshape(-1, d)[:n].reshape(b, s, d)
    return x + out, aux


def route(h: jnp.ndarray, p: Params, cfg
          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k routing over every expert, in float32 (DeepSeek-V3's
    ``noaux_tc`` with one group). h: (N, D) -> the chosen experts' global
    ids (N, k) and their weights (N, k): sigmoid scores; the top-k of
    score + ``router_bias`` are chosen, and weighted by their scores
    without the bias, normalised, times ``cfg.routed_scale``."""
    logits = jnp.einsum("nd,de->ne", h.astype(jnp.float32), p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + p["router_bias"], cfg.moe_top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * cfg.routed_scale


def _swiglu(h: jnp.ndarray, p: Params) -> jnp.ndarray:
    return dense(jax.nn.silu(dense(h, p["w_gate"])) * dense(h, p["w_up"]),
                 p["w_down"])


# the counters :func:`moe_dropless` returns, in this order
COUNTERS = ("pairs", "experts_hit", "max_load")
# gmm's row tile; a contraction or output tile of 512 where the width
# takes it, else the whole width (tiles measured on a v5e at the held
# experts' shapes, PERF.md)
ROW_TILE, WIDTH_TILE = 128, 512


def grouped_matmul(rows: jnp.ndarray, w: jnp.ndarray,
                   sizes: jnp.ndarray) -> jnp.ndarray:
    """rows (M, K) sorted by group, w (G, K, N), sizes (G,): each group's
    rows times its matrix, in float32. Rows past the groups are not
    computed and hold no defined value."""
    m, k = rows.shape
    tile = lambda n: WIDTH_TILE if n % WIDTH_TILE == 0 else n
    pad = -m % ROW_TILE
    out = gmm(jnp.pad(rows, ((0, pad), (0, 0))), w, sizes, jnp.float32,
              tiling=(ROW_TILE, tile(k), tile(w.shape[-1])),
              interpret=interpret_mode())
    return out[:m]


def _sorted_experts(t, key, onehot, w, p, dtype):
    """The pairs on held experts, sorted by expert, through ``gmm``. t (N,
    D); per pair (N * k): ``key`` its held expert's index (``held`` where
    it is not held), ``onehot`` (N * k, held + 1) of it, ``w`` its weight.
    Returns (N, D) in float32."""
    n, d = t.shape
    held = onehot.shape[1] - 1
    k = key.shape[0] // n
    # a counting sort of the pairs by expert, those on held experts first:
    # ``dest`` is each pair's place in the sorted order (a stable sort over
    # held + 1 keys, without a comparison sort's compile time)
    count = jnp.sum(onehot, axis=0)
    dest = (jnp.cumsum(count) - count)[key] + jnp.take_along_axis(
        jnp.cumsum(onehot, axis=0) - onehot, key[:, None], axis=1)[:, 0]
    order = jnp.zeros_like(dest).at[dest].set(jnp.arange(n * k))
    # rows past the held groups are zeros in, and masked out
    computed = (key < held)[order][:, None]
    rows = jnp.where(computed, t[order // k], 0)
    with jax.named_scope("experts"):
        dot = partial(grouped_matmul, sizes=count[:held])
        g = dot(rows, p["w_gate"].astype(dtype))
        u = dot(rows, p["w_up"].astype(dtype))
        y = dot((jax.nn.silu(g) * u).astype(dtype), p["w_down"].astype(dtype))
    y = jnp.where(computed, y * w[order][:, None], 0.0)
    return y[dest].reshape(n, k, d).sum(axis=1)


def _masked_experts(t, onehot, w, p, dtype):
    """Every held expert on every token, each weighted by its routing
    weight (0 where the token did not choose it); arguments as
    :func:`_sorted_experts`. Returns (N, D) in float32."""
    n = t.shape[0]
    held = onehot.shape[1] - 1
    gate = jnp.einsum("nke,nk->ne",
                      onehot[:, :held].reshape(n, -1, held).astype(w.dtype),
                      w.reshape(n, -1))
    with jax.named_scope("experts"):
        up = partial(jnp.einsum, "nd,edf->nef",
                     preferred_element_type=jnp.float32)
        a = (jax.nn.silu(up(t, p["w_gate"].astype(dtype)))
             * up(t, p["w_up"].astype(dtype)) * gate[..., None])
        return jnp.einsum("nef,efd->nd", a.astype(dtype),
                          p["w_down"].astype(dtype),
                          preferred_element_type=jnp.float32)


def moe_dropless(x: jnp.ndarray, p: Params, cfg
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dropless MoE over the held experts. x: (B, S, D) -> (x + out,
    counters), the counters an int32 (3,) of :data:`COUNTERS`: the
    (token, expert) pairs routed to held experts, the held experts that
    got at least one, and the most pairs on one held expert."""
    b, s, d = x.shape
    h = rmsnorm(x, p["ln"], cfg.rms_eps)
    t = h.reshape(-1, d)
    idx, w = route(t, p, cfg)
    held = p["w_gate"].shape[0]
    local = (idx - cfg.first_expert).reshape(-1)
    key = jnp.where((local >= 0) & (local < held), local, held)
    onehot = jax.nn.one_hot(key, held + 1, dtype=jnp.int32)
    w = jnp.where(key < held, w.reshape(-1), 0.0)
    if t.shape[0] < ROW_TILE:
        out = _masked_experts(t, onehot, w, p, x.dtype)
    else:
        out = _sorted_experts(t, key, onehot, w, p, x.dtype)
    if "shared" in p:
        out = out + _swiglu(t, p["shared"]).astype(jnp.float32)
    sizes = jnp.sum(onehot[:, :held], axis=0)
    counts = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0, dtype=jnp.int32),
                        jnp.max(sizes)])
    return x + out.astype(x.dtype).reshape(b, s, d), counts


def moe_block(x: jnp.ndarray, p: Params, cfg
              ) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[jnp.ndarray]]:
    """(x + out, aux loss, counters or None)."""
    if cfg.moe_impl == "dropless":
        x, counts = moe_dropless(x, p, cfg)
        return x, jnp.zeros((), jnp.float32), counts
    if cfg.moe_impl == "dense":
        return (*moe_dense(x, p, cfg.moe_top_k), None)
    return (*moe_dropping(x, p, cfg.moe_top_k, cfg.moe_capacity_factor,
                          cfg.moe_group_size), None)
