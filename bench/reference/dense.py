"""Plain reference of a dense decoder-only transformer: grouped-query
attention with rotary positions (half-split rotation), RMSNorm before each
sub-layer, a SwiGLU feed-forward, optional biases on Q, K and V, a final
RMSNorm and an output head, its own or the embedding's transpose (tied).

Written from the published description in straightforward ``jax.numpy``;
it imports nothing of the system under test. In the default mode every
operation is float32 and every matrix product runs at
``Precision.HIGHEST`` (six bf16 passes on a TPU), so rounding is that of
float32 alone.

Weights come from the seed through :func:`init_globals` and
:func:`init_layer`, the same functions the harness uses to make the served
weights, so the reference never reads an array the program made. Each
layer's weights are drawn from ``fold_in(key, layer)``, which lets the
forward pass make one layer at a time (:func:`served_logit_gaps`).

Modes below float32 (``bf16``, ``int8``, ``fp8``) are the controls that
the correctness comparison must refuse: ``bf16`` casts weights and
activations to bfloat16 around every product and keeps parameters and
Adam moments in bfloat16; ``int8`` quantizes weights per output channel
and activations per token, symmetrically to 127 levels, before every
product; ``fp8`` does the same onto float8 e4m3 with the largest value
scaled to 448 (both simulated in float32).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "bq", "bk", "bv", "wo",
                "mlp_norm", "w_gate", "w_up", "w_down")
GLOBAL_LEAVES = ("embed", "final_norm", "lm_head")
MODES = ("f32", "bf16", "int8", "fp8")


@dataclasses.dataclass(frozen=True)
class Dense:
    vocab: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    qkv_bias: bool
    rope_theta: float
    rms_eps: float
    tied: bool = False          # the output head is the embedding's transpose

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @classmethod
    def from_config(cls, c: dict) -> "Dense":
        return cls(vocab=c["vocab_size"], d_model=c["hidden_size"],
                   n_layers=c["num_hidden_layers"],
                   n_heads=c["num_attention_heads"],
                   n_kv_heads=c["num_key_value_heads"],
                   d_ff=c["intermediate_size"], qkv_bias=c["qkv_bias"],
                   rope_theta=float(c["rope_theta"]),
                   rms_eps=float(c["rms_norm_eps"]),
                   tied=bool(c["tie_word_embeddings"]))

    def layer_shapes(self) -> Dict[str, Tuple[int, ...]]:
        d, q, kv, f = (self.d_model, self.n_heads * self.head_dim,
                       self.n_kv_heads * self.head_dim, self.d_ff)
        shapes = {"attn_norm": (d,), "wq": (d, q), "wk": (d, kv),
                  "wv": (d, kv), "bq": (q,), "bk": (kv,), "bv": (kv,),
                  "wo": (q, d), "mlp_norm": (d,), "w_gate": (d, f),
                  "w_up": (d, f), "w_down": (f, d)}
        if not self.qkv_bias:
            for b in ("bq", "bk", "bv"):
                shapes.pop(b)
        return shapes

    def global_shapes(self) -> Dict[str, Tuple[int, ...]]:
        return {"embed": (self.vocab, self.d_model),
                "final_norm": (self.d_model,),
                "lm_head": (self.d_model, self.vocab)}


# ------------------------------------------------------------------ weights

def seed_key(seed: int) -> jax.Array:
    """A key for any whole number up to 64 bits (``PRNGKey`` alone keeps
    only the low 32)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _draw(key, name: str, shape, dtype):
    """Norm weights 1 + 0.1 N(0,1), biases 0.2 N(0,1), matrices
    N(0,1) / sqrt(fan_in): drawn in float32, stored in ``dtype``."""
    z = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("norm"):
        w = 1.0 + 0.1 * z
    elif name in ("bq", "bk", "bv"):
        w = 0.2 * z
    elif name == "embed":
        w = z / math.sqrt(shape[1])
    else:
        w = z / math.sqrt(shape[0])
    return w.astype(dtype)


def init_layer(key, i, spec: Dense, dtype) -> Dict[str, jax.Array]:
    """Layer ``i``'s weights; ``i`` may be traced (``vmap`` over layers)."""
    k = jax.random.fold_in(jax.random.fold_in(key, 1), i)
    return {n: _draw(jax.random.fold_in(k, LAYER_LEAVES.index(n)), n, s,
                     dtype)
            for n, s in spec.layer_shapes().items()}


def init_globals(key, spec: Dense, dtype) -> Dict[str, jax.Array]:
    """Embedding, final norm and output head; a tied head is the
    embedding's transpose, drawn once."""
    k = jax.random.fold_in(key, 0)
    g = {n: _draw(jax.random.fold_in(k, GLOBAL_LEAVES.index(n)), n, s,
                  dtype)
         for n, s in spec.global_shapes().items()
         if not (spec.tied and n == "lm_head")}
    if spec.tied:
        g["lm_head"] = g["embed"].T
    return g


def init_params(key, spec: Dense, dtype):
    """Whole model: globals plus layers stacked on a leading axis."""
    layers = jax.vmap(lambda i: init_layer(key, i, spec, dtype))(
        jnp.arange(spec.n_layers))
    return {**init_globals(key, spec, dtype), "layers": layers}


# ------------------------------------------------------------- arithmetic

def _quant_int8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(x / s).clip(-127, 127) * s


def _quant_fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(x, w, mode: str):
    """x (..., k) @ w (k, n) in the arithmetic of ``mode``."""
    if mode == "f32":
        return jnp.einsum("...k,kn->...n", x, w,
                          precision=jax.lax.Precision.HIGHEST)
    if mode == "bf16":
        return jnp.einsum("...k,kn->...n", x.astype(jnp.bfloat16),
                          w.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32
                          ).astype(jnp.bfloat16).astype(jnp.float32)
    if mode in ("int8", "fp8"):
        q = _quant_int8 if mode == "int8" else _quant_fp8
        return jnp.einsum("...k,kn->...n", q(x, -1), q(w, 0),
                          precision=jax.lax.Precision.HIGHEST)
    raise ValueError(f"unknown mode {mode!r}; one of {MODES}")


def _round(x, mode):
    return x.astype(jnp.bfloat16).astype(jnp.float32) if mode == "bf16" \
        else x


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x (S, H, D), pos (S,): rotate the two halves of each head."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v, chunk: int, mode: str):
    """One sequence: q (S, H, D), k and v (S, KV, D); query head h reads
    key/value head h // (H // KV). Queries go ``chunk`` at a time so the
    score block stays (H, chunk, S)."""
    s_len, h, d = q.shape
    group = h // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    chunk = min(chunk, s_len)
    n = s_len // chunk
    assert n * chunk == s_len, (s_len, chunk)
    prec = (jax.lax.Precision.HIGHEST if mode != "bf16"
            else jax.lax.Precision.DEFAULT)

    def one(args):
        qc, c0 = args
        sc = jnp.einsum("qhd,khd->hqk", qc, k, precision=prec) / math.sqrt(d)
        rows = c0 + jnp.arange(chunk)
        sc = jnp.where(jnp.arange(s_len)[None, None, :] <= rows[None, :,
                                                                None],
                       sc, -jnp.inf)
        p = _round(jax.nn.softmax(sc, axis=-1), mode)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=prec)

    out = jax.lax.map(one, (q.reshape(n, chunk, h, d),
                            jnp.arange(n) * chunk))
    return out.reshape(s_len, h, d)


def block(x, w, spec: Dense, mode: str, attn_chunk: int = 512):
    """One layer over x (N, S, d), float32 in and out."""
    n, s_len, _ = x.shape
    pos = jnp.arange(s_len)
    h = _round(rmsnorm(x, w["attn_norm"], spec.rms_eps), mode)

    def proj(name, bias, heads):
        y = mm(h, w[name], mode)
        if spec.qkv_bias:
            y = y + w[bias]
        return _round(y, mode).reshape(n, s_len, heads, spec.head_dim)

    q = proj("wq", "bq", spec.n_heads)
    k = proj("wk", "bk", spec.n_kv_heads)
    v = proj("wv", "bv", spec.n_kv_heads)
    q = _round(jax.vmap(lambda t: rope(t, pos, spec.rope_theta))(q), mode)
    k = _round(jax.vmap(lambda t: rope(t, pos, spec.rope_theta))(k), mode)
    a = jax.vmap(lambda q, k, v: causal_attention(q, k, v, attn_chunk,
                                                  mode))(q, k, v)
    a = _round(a, mode).reshape(n, s_len, -1)
    x = _round(x + mm(a, w["wo"], mode), mode)
    h = _round(rmsnorm(x, w["mlp_norm"], spec.rms_eps), mode)
    g = _round(jax.nn.silu(mm(h, w["w_gate"], mode)), mode)
    u = _round(mm(h, w["w_up"], mode), mode)
    return _round(x + mm(_round(g * u, mode), w["w_down"], mode), mode)


# ---------------------------------------------------------------- serving

@partial(jax.jit, static_argnames=("spec", "dtype"))
def _embed(key, tokens, spec, dtype):
    return init_globals(key, spec, dtype)["embed"][tokens].astype(
        jnp.float32)


@partial(jax.jit, static_argnames=("spec", "dtype", "mode"))
def _layer(x, key, i, spec, dtype, mode):
    w = jax.tree.map(lambda a: a.astype(jnp.float32),
                     init_layer(key, i, spec, dtype))
    return block(x, w, spec, mode)


@partial(jax.jit, static_argnames=("spec", "dtype", "mode", "chunk"))
def _head(x, idx, tok, key, spec, dtype, mode, chunk=128):
    """Logits at positions ``idx`` (N, P): returns the largest logit, the
    logit of ``tok`` (N, P) and the first-ranked token, each (N, P)."""
    g = jax.tree.map(lambda a: a.astype(jnp.float32),
                     init_globals(key, spec, dtype))
    h = jnp.take_along_axis(x, idx[..., None], axis=1)
    h = _round(rmsnorm(h, g["final_norm"], spec.rms_eps), mode)
    n, p, d = h.shape
    nc = p // chunk

    def one(args):
        hc, tc = args
        lg = mm(hc, g["lm_head"], mode)
        return (lg.max(-1), jnp.take_along_axis(lg, tc[..., None], -1)[..., 0],
                lg.argmax(-1).astype(jnp.int32))

    out = jax.lax.map(one, (h.reshape(n, nc, chunk, d).swapaxes(0, 1),
                            tok.reshape(n, nc, chunk).swapaxes(0, 1)))
    return tuple(o.swapaxes(0, 1).reshape(n, p) for o in out)


def _forward_hidden(key, tokens, spec, dtype, mode):
    x = _embed(key, jnp.asarray(tokens), spec, dtype)
    if mode == "bf16":
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
    for i in range(spec.n_layers):
        x = _layer(x, key, jnp.int32(i), spec, dtype, mode)
    return x


def pack(seqs: Sequence[Tuple[np.ndarray, np.ndarray]], pad_to: int,
         chunk: int = 128):
    """``seqs``: (prompt, served tokens). Row j holds prompt + served[:-1],
    padded to ``pad_to``; the served token k was ranked first at position
    len(prompt) - 1 + k. Returns tokens (N, pad_to), positions and served
    tokens (N, P) padded to a multiple of ``chunk``, and the counts."""
    n = len(seqs)
    p = max(len(s) for _, s in seqs)
    p = -(-p // chunk) * chunk
    tokens = np.zeros((n, pad_to), np.int32)
    idx = np.zeros((n, p), np.int32)
    tok = np.zeros((n, p), np.int32)
    counts = []
    for j, (prompt, served) in enumerate(seqs):
        row = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        assert len(row) <= pad_to, (len(row), pad_to)
        tokens[j, :len(row)] = row
        m = len(served)
        idx[j, :m] = len(prompt) - 1 + np.arange(m)
        idx[j, m:] = idx[j, m - 1]
        tok[j, :m] = served
        tok[j, m:] = served[-1]
        counts.append(m)
    return tokens, idx, tok, counts


def served_logit_gaps(spec: Dense, seed: int, seqs, pad_to: int,
                      dtype=jnp.bfloat16, mode: str = "f32"
                      ) -> List[np.ndarray]:
    """For each served token: how far its float32 reference logit lies below
    the reference's largest logit at that position (0 where the served token
    ranks first). With ``mode`` below float32 the served tokens are replaced
    by what that arithmetic ranks first at each position of the same
    sequences: the control's reading."""
    key = seed_key(seed)
    tokens, idx, tok, counts = pack(seqs, pad_to)
    if mode != "f32":
        xq = _forward_hidden(key, tokens, spec, dtype, mode)
        tok = np.asarray(_head(xq, idx, tok, key, spec, dtype, mode)[2])
        del xq
    with jax.default_matmul_precision("highest"):
        x = _forward_hidden(key, tokens, spec, dtype, "f32")
        top, got, _ = _head(x, idx, tok, key, spec, dtype, "f32")
    gap = np.asarray(top - got, np.float64)
    return [gap[j, :m] for j, m in enumerate(counts)]


# --------------------------------------------------------------- training

@dataclasses.dataclass(frozen=True)
class AdamW:
    """AdamW as the training configuration states it: the gradient clipped
    to a global norm, bias-corrected moments, decoupled weight decay on
    every parameter, and a learning rate warmed up linearly over
    ``warmup`` steps, then a cosine down to ``final_frac`` of its peak."""
    lr: float
    warmup: int
    total: int
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.01
    clip_norm: float = 1.0
    final_frac: float = 0.1

    def lr_at(self, t):
        t = jnp.asarray(t, jnp.float32)
        prog = jnp.clip((t - self.warmup) / max(self.total - self.warmup, 1),
                        0.0, 1.0)
        cos = self.final_frac + (1 - self.final_frac) * 0.5 * (
            1 + jnp.cos(jnp.pi * prog))
        return jnp.where(t < self.warmup, self.lr * t / max(self.warmup, 1),
                         self.lr * cos)


def _row_loss(params, tokens, labels, spec, mode):
    """Summed next-token cross entropy of one row and its count."""
    g = {k: params[k] for k in GLOBAL_LEAVES}
    x = _round(g["embed"][tokens].astype(jnp.float32), mode)[None]

    def body(x, w):
        return block(x, w, spec, mode), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, params["layers"])
    h = _round(rmsnorm(x[0], g["final_norm"], spec.rms_eps), mode)
    logits = mm(h, g["lm_head"], mode)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[:, None],
                               -1)[:, 0]
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum((lse - gold) * mask), jnp.sum(mask)


@partial(jax.jit, static_argnames=("spec", "mode"))
def loss_and_grad(params, tokens, labels, spec, mode):
    """Mean loss over the batch and its gradient, one row at a time."""
    f32 = jax.tree.map(lambda p: p.astype(jnp.float32), params)

    def row(carry, tl):
        nll, cnt, gsum = carry
        (l, c), g = jax.value_and_grad(_row_loss, has_aux=True)(
            f32, tl[0], tl[1], spec, mode)
        return (nll + l, cnt + c, jax.tree.map(jnp.add, gsum, g)), None

    zeros = jax.tree.map(jnp.zeros_like, f32)
    (nll, cnt, gsum), _ = jax.lax.scan(
        row, (jnp.float32(0), jnp.float32(0), zeros), (tokens, labels))
    return nll / cnt, jax.tree.map(lambda g: g / cnt, gsum)


@partial(jax.jit, static_argnames=("opt", "mode"))
def adam_step(params, grads, m, v, t, opt: AdamW, mode):
    """One AdamW update; ``t`` counts from 1. In ``bf16`` the parameters
    and moments stay in bfloat16 between steps."""
    sq = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))
    scale = jnp.minimum(1.0, opt.clip_norm / (jnp.sqrt(sq) + 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    store = jnp.bfloat16 if mode == "bf16" else jnp.float32
    m = jax.tree.map(lambda m, g: (opt.b1 * m + (1 - opt.b1) * g
                                   ).astype(store), m, grads)
    v = jax.tree.map(lambda v, g: (opt.b2 * v + (1 - opt.b2) * g * g
                                   ).astype(store), v, grads)
    tf = jnp.asarray(t, jnp.float32)
    lr = opt.lr_at(tf)

    def upd(p, m, v):
        mh = m.astype(jnp.float32) / (1 - opt.b1 ** tf)
        vh = v.astype(jnp.float32) / (1 - opt.b2 ** tf)
        p32 = p.astype(jnp.float32)
        return (p32 - lr * (mh / (jnp.sqrt(vh) + opt.eps)
                            + opt.weight_decay * p32)).astype(store)

    return jax.tree.map(upd, params, m, v), m, v, grads


def leaf_norms(tree) -> Dict[str, np.ndarray]:
    """Euclidean norm of each leaf; a stacked layer leaf gives one norm per
    layer. Keys are the reference's leaf names."""
    out = {k: np.asarray(jnp.sqrt(jnp.sum(jnp.square(
        tree[k].astype(jnp.float32)))))[None] for k in GLOBAL_LEAVES}
    for k, a in tree["layers"].items():
        a = a.astype(jnp.float32)
        out[k] = np.asarray(jnp.sqrt(jnp.sum(jnp.square(a),
                                             axis=tuple(range(1, a.ndim)))))
    return out


def train_reference(spec: Dense, seed: int, batches, opt: AdamW,
                    mode: str = "f32", dtype=jnp.float32):
    """Follow the first ``len(batches)`` steps from the seeded weights.

    Returns the loss of each step, the norms of the clipped first gradient
    (what the optimizer is given) and the norms of the parameters' change
    over all the steps, per leaf (:func:`leaf_norms`)."""
    if spec.tied:
        raise ValueError("the training reference keeps its own output head")
    key = seed_key(seed)
    store = jnp.bfloat16 if mode == "bf16" else jnp.float32
    params = jax.jit(partial(init_params, spec=spec, dtype=dtype))(key)
    params = jax.tree.map(lambda p: p.astype(store), params)
    p0 = jax.tree.map(jnp.copy, params)
    m = jax.tree.map(lambda p: jnp.zeros(p.shape, store), params)
    v = jax.tree.map(lambda p: jnp.zeros(p.shape, store), params)
    losses, g1 = [], None
    with jax.default_matmul_precision("highest"):
        for t, (tokens, labels) in enumerate(batches, start=1):
            loss, grads = loss_and_grad(params, jnp.asarray(tokens),
                                        jnp.asarray(labels), spec, mode)
            params, m, v, clipped = adam_step(params, grads, m, v, t, opt,
                                              mode)
            losses.append(float(loss))
            if g1 is None:
                g1 = leaf_norms(clipped)
            del grads, clipped
    change = leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        params, p0))
    return losses, g1, change
