"""Plain reference of a DeepSeek-V3-style decoder (Moonlight-16B-A3B):
multi-head latent attention without a query latent, leading dense layers,
then layers whose feed-forward is a mixture of experts with sigmoid
routing, a selection bias and shared experts; RMSNorm before each
sub-layer, a final RMSNorm and an untied output head. Serving only.

Attention, per layer: ``q = x Wq`` gives each head ``nope + rope`` dims;
``[c, k_pe] = x Wkv_a``; ``c = RMSNorm(c)``; ``[k_nope, v] = c Wkv_b``
per head; rotary positions on ``q_pe`` and on ``k_pe``, which every head
shares; scores scaled by 1 / sqrt(nope + rope); the output projection
from the heads' values. The published weights interleave the rope
dimensions (each pair of neighbours rotates together); this reference and
the program both rotate the two halves of the rope dimensions, which is
the published model with those 64 columns of ``Wq`` and ``Wkv_a``
permuted once: the same family of models, so random weights lose nothing
by it.

Experts, per MoE layer: scores ``sigmoid(h Wr)`` over every routed expert
in float32; the top-k of score + selection bias are chosen; each chosen
expert is weighted by its score without the bias, the k weights
normalised to sum to one and scaled by ``routed_scale``. The layer holds
``held`` routed experts from id ``first`` on: it adds what those experts
give, weighted as routed, and nothing for the others, as one chip of an
expert-parallel deployment does before its exchange; every held expert
runs on every token and the routing's weight masks it (no sort, no
capacity). The shared experts, one SwiGLU, apply to every token.

Written from the published description in straightforward ``jax.numpy``;
it imports nothing of the system under test. In the default mode every
operation is float32 and every matrix product runs at
``Precision.HIGHEST``, so rounding is that of float32 alone. Modes below
float32 (``bf16``, ``int8``, ``fp8``) are the controls the correctness
comparison must refuse: ``bf16`` rounds weights and activations to
bfloat16 around every product; ``int8`` quantizes weights per output
channel and activations per token, symmetrically to 127 levels, before
every product; ``fp8`` does the same onto float8 e4m3 scaled to 448 (both
simulated in float32). The router's product is held to the mode too.

Weights come from the seed through :func:`init_globals` and
:func:`init_layer`, the same functions the harness uses to make the served
weights. Layer ``i`` is drawn from ``fold_in(key, i)`` and routed expert
``e`` of it from ``fold_in`` of that by its global id, so a share of the
experts is a slice of one model whatever share is held.

The module is also the harness's whole description of the family (the
last section; ``bench/program.py`` lists the names it takes). It reads
the program's configuration object and parameter tree by their attribute
and key names only.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

ATTN_LEAVES = ("attn_norm", "wq", "wkv_a", "kv_norm", "wkv_b", "wo",
               "mlp_norm")
DENSE_LEAVES = ("w_gate", "w_up", "w_down")
MOE_LEAVES = ("router", "router_bias", "shared_gate", "shared_up",
              "shared_down", "expert_gate", "expert_up", "expert_down")
LAYER_LEAVES = ATTN_LEAVES + DENSE_LEAVES + MOE_LEAVES
EXPERT_LEAVES = ("expert_gate", "expert_up", "expert_down")
FLOAT32_LEAVES = ("router", "router_bias")   # held in float32 when served
GLOBAL_LEAVES = ("embed", "final_norm", "lm_head")
MODES = ("f32", "bf16", "int8", "fp8")
BIAS_SCALE = 0.05     # the selection bias: N(0, 1) times this


@dataclasses.dataclass(frozen=True)
class MlaMoe:
    vocab: int
    d_model: int
    n_layers: int
    n_dense: int            # leading dense layers
    n_heads: int
    kv_lora: int
    nope: int
    rope: int
    v_dim: int
    d_ff: int               # the dense layers' SwiGLU
    d_expert: int
    n_experts: int          # routed experts: the router's width
    n_shared: int
    top_k: int
    routed_scale: float
    norm_topk: bool
    held: int               # routed experts held here, ids first..
    first: int
    rope_theta: float
    rms_eps: float

    @classmethod
    def from_config(cls, c: dict) -> "MlaMoe":
        """The model a configuration file runs: the published shape with
        the held share of the routed experts."""
        return cls(vocab=c["vocab_size"], d_model=c["hidden_size"],
                   n_layers=c["num_hidden_layers"],
                   n_dense=c["first_k_dense_replace"],
                   n_heads=c["num_attention_heads"],
                   kv_lora=c["kv_lora_rank"], nope=c["qk_nope_head_dim"],
                   rope=c["qk_rope_head_dim"], v_dim=c["v_head_dim"],
                   d_ff=c["intermediate_size"],
                   d_expert=c["moe_intermediate_size"],
                   n_experts=c["source_values"]["n_routed_experts"],
                   n_shared=c["n_shared_experts"],
                   top_k=c["num_experts_per_tok"],
                   routed_scale=float(c["routed_scaling_factor"]),
                   norm_topk=bool(c["norm_topk_prob"]),
                   held=c["n_routed_experts"],
                   first=c["first_routed_expert"],
                   rope_theta=float(c["rope_theta"]),
                   rms_eps=float(c["rms_norm_eps"]))

    @property
    def qk_dim(self) -> int:
        return self.nope + self.rope

    def attn_shapes(self) -> Dict[str, Tuple[int, ...]]:
        d, h = self.d_model, self.n_heads
        return {"attn_norm": (d,), "wq": (d, h * self.qk_dim),
                "wkv_a": (d, self.kv_lora + self.rope),
                "kv_norm": (self.kv_lora,),
                "wkv_b": (self.kv_lora, h * (self.nope + self.v_dim)),
                "wo": (h * self.v_dim, d), "mlp_norm": (d,)}

    def dense_shapes(self) -> Dict[str, Tuple[int, ...]]:
        d, f = self.d_model, self.d_ff
        return {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}

    def moe_shapes(self) -> Dict[str, Tuple[int, ...]]:
        """One layer's MoE leaves; an expert leaf is one expert's."""
        d, f, s = self.d_model, self.d_expert, self.n_shared * self.d_expert
        return {"router": (d, self.n_experts),
                "router_bias": (self.n_experts,),
                "shared_gate": (d, s), "shared_up": (d, s),
                "shared_down": (s, d), "expert_gate": (d, f),
                "expert_up": (d, f), "expert_down": (f, d)}

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
    """Norm weights 1 + 0.1 N(0,1), the selection bias ``BIAS_SCALE``
    N(0,1), matrices N(0,1) / sqrt(fan_in): drawn in float32, stored in
    ``dtype`` (the router and its bias in float32)."""
    z = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("norm"):
        w = 1.0 + 0.1 * z
    elif name == "router_bias":
        w = BIAS_SCALE * z
    elif name == "embed":
        w = z / math.sqrt(shape[1])
    else:
        w = z / math.sqrt(shape[0])
    return w if name in FLOAT32_LEAVES else w.astype(dtype)


def _layer_key(key, i):
    return jax.random.fold_in(jax.random.fold_in(key, 1), i)


def _leaves(k, shapes: dict, dtype) -> Dict[str, jax.Array]:
    return {n: _draw(jax.random.fold_in(k, LAYER_LEAVES.index(n)), n, s,
                     dtype) for n, s in shapes.items()}


def init_layer(key, i, spec: MlaMoe, dtype, moe: bool
               ) -> Dict[str, jax.Array]:
    """Layer ``i``'s weights (``i`` may be traced): attention, then the
    dense SwiGLU or (``moe``) the router, its bias, the shared experts and
    the held routed experts stacked on a leading axis."""
    k = _layer_key(key, i)
    w = _leaves(k, spec.attn_shapes(), dtype)
    if not moe:
        return {**w, **_leaves(k, spec.dense_shapes(), dtype)}
    shapes = spec.moe_shapes()
    w.update(_leaves(k, {n: s for n, s in shapes.items()
                         if n not in EXPERT_LEAVES}, dtype))
    ids = spec.first + jnp.arange(spec.held)
    for n in EXPERT_LEAVES:
        kn = jax.random.fold_in(k, LAYER_LEAVES.index(n))
        w[n] = jax.vmap(lambda e: _draw(jax.random.fold_in(kn, e), n,
                                        shapes[n], dtype))(ids)
    return w


def init_globals(key, spec: MlaMoe, dtype) -> Dict[str, jax.Array]:
    k = jax.random.fold_in(key, 0)
    return {n: _draw(jax.random.fold_in(k, GLOBAL_LEAVES.index(n)), n, s,
                     dtype) for n, s in spec.global_shapes().items()}


def init_params(key, spec: MlaMoe, dtype):
    """Whole model: globals, the dense layers stacked (``lead``) and the
    MoE layers stacked (``layers``)."""
    lead = jax.vmap(lambda i: init_layer(key, i, spec, dtype, False))(
        jnp.arange(spec.n_dense))
    layers = jax.vmap(lambda i: init_layer(key, i, spec, dtype, True))(
        jnp.arange(spec.n_dense, spec.n_layers))
    return {**init_globals(key, spec, dtype), "lead": lead,
            "layers": layers}


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
    """One sequence: q and k (S, H, Dqk), v (S, H, Dv), scores scaled by
    1 / sqrt(Dqk). Queries go ``chunk`` at a time so the score block stays
    (H, chunk, S)."""
    s_len, h, d = q.shape
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
    return out.reshape(s_len, h, v.shape[-1])


def attention(x, w, spec: MlaMoe, mode: str, attn_chunk: int = 512):
    """The attention sub-layer over x (N, S, d), with its residual."""
    n, s_len, _ = x.shape
    pos = jnp.arange(s_len)
    ro = jax.vmap(lambda t: rope(t, pos, spec.rope_theta))
    h = _round(rmsnorm(x, w["attn_norm"], spec.rms_eps), mode)
    q = _round(mm(h, w["wq"], mode), mode).reshape(
        n, s_len, spec.n_heads, spec.qk_dim)
    q = jnp.concatenate([q[..., :spec.nope],
                         _round(ro(q[..., spec.nope:]), mode)], -1)
    kva = _round(mm(h, w["wkv_a"], mode), mode)
    c = _round(rmsnorm(kva[..., :spec.kv_lora], w["kv_norm"], spec.rms_eps),
               mode)
    k_pe = _round(ro(kva[..., None, spec.kv_lora:]), mode)
    kv = _round(mm(c, w["wkv_b"], mode), mode).reshape(
        n, s_len, spec.n_heads, spec.nope + spec.v_dim)
    k = jnp.concatenate([kv[..., :spec.nope], jnp.broadcast_to(
        k_pe, (n, s_len, spec.n_heads, spec.rope))], -1)
    a = jax.vmap(lambda q, k, v: causal_attention(q, k, v, attn_chunk,
                                                  mode))(
        q, k, kv[..., spec.nope:])
    a = _round(a, mode).reshape(n, s_len, -1)
    return _round(x + mm(a, w["wo"], mode), mode)


def _swiglu(h, wg, wu, wd, mode):
    g = _round(jax.nn.silu(mm(h, wg, mode)), mode)
    u = _round(mm(h, wu, mode), mode)
    return mm(_round(g * u, mode), wd, mode)


def route(h, w, spec: MlaMoe, mode: str = "f32"):
    """The chosen routed experts' global ids (..., k) and weights (..., k)
    of the normed inputs ``h``."""
    scores = jax.nn.sigmoid(mm(h, w["router"], mode))
    _, idx = jax.lax.top_k(scores + w["router_bias"], spec.top_k)
    wt = jnp.take_along_axis(scores, idx, axis=-1)
    if spec.norm_topk:
        wt = wt / (jnp.sum(wt, -1, keepdims=True) + 1e-20)
    return idx, wt * spec.routed_scale


def routed_weights(h, w, spec: MlaMoe, mode: str = "f32"):
    """Each held expert's weight per token (..., held): its routing weight
    where the token chose it, else 0."""
    idx, wt = route(h, w, spec, mode)
    full = jnp.sum(jax.nn.one_hot(idx, spec.n_experts) * wt[..., None],
                   axis=-2)
    return full[..., spec.first:spec.first + spec.held]


def moe(x, w, spec: MlaMoe, mode: str):
    """The expert sub-layer over x (N, S, d), with its residual: every
    held expert on every token, masked by the routing's weight, plus the
    shared experts."""
    h = _round(rmsnorm(x, w["mlp_norm"], spec.rms_eps), mode)
    gate = routed_weights(h, w, spec, mode)

    def one(acc, e):
        wg, wu, wd, g = e
        return acc + g[..., None] * _swiglu(h, wg, wu, wd, mode), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (w["expert_gate"], w["expert_up"], w["expert_down"],
         jnp.moveaxis(gate, -1, 0)))
    shared = _swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"],
                     mode)
    return _round(x + _round(routed, mode) + _round(shared, mode), mode)


def dense_mlp(x, w, spec: MlaMoe, mode: str):
    h = _round(rmsnorm(x, w["mlp_norm"], spec.rms_eps), mode)
    return _round(x + _swiglu(h, w["w_gate"], w["w_up"], w["w_down"], mode),
                  mode)


def block(x, w, spec: MlaMoe, mode: str, moe_layer: bool):
    """One layer over x (N, S, d), float32 in and out."""
    x = attention(x, w, spec, mode)
    return moe(x, w, spec, mode) if moe_layer else dense_mlp(x, w, spec,
                                                             mode)


# ---------------------------------------------------------------- serving

@partial(jax.jit, static_argnames=("spec", "dtype"))
def _embed(key, tokens, spec, dtype):
    return init_globals(key, spec, dtype)["embed"][tokens].astype(
        jnp.float32)


@partial(jax.jit, static_argnames=("spec", "dtype", "mode", "moe_layer"))
def _layer(x, key, i, spec, dtype, mode, moe_layer):
    w = jax.tree.map(lambda a: a.astype(jnp.float32),
                     init_layer(key, i, spec, dtype, moe_layer))
    return block(x, w, spec, mode, moe_layer)


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


def forward_hidden(key, tokens, spec: MlaMoe, dtype, mode: str = "f32"):
    """The final layer's output (N, S, d), one layer at a time."""
    x = _embed(key, jnp.asarray(tokens), spec, dtype)
    if mode == "bf16":
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
    for i in range(spec.n_layers):
        x = _layer(x, key, jnp.int32(i), spec, dtype, mode,
                   i >= spec.n_dense)
    return x


def logits(key, tokens, spec: MlaMoe, dtype, mode: str = "f32"):
    """Every position's logits (N, S, V): the full forward, for tests at
    small sizes."""
    g = jax.tree.map(lambda a: a.astype(jnp.float32),
                     init_globals(key, spec, dtype))
    x = forward_hidden(key, tokens, spec, dtype, mode)
    return mm(_round(rmsnorm(x, g["final_norm"], spec.rms_eps), mode),
              g["lm_head"], mode)


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


def served_logit_gaps(spec: MlaMoe, seed: int, seqs, pad_to: int,
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
        xq = forward_hidden(key, tokens, spec, dtype, mode)
        tok = np.asarray(_head(xq, idx, tok, key, spec, dtype, mode)[2])
        del xq
    with jax.default_matmul_precision("highest"):
        x = forward_hidden(key, tokens, spec, dtype, "f32")
        top, got, _ = _head(x, idx, tok, key, spec, dtype, "f32")
    gap = np.asarray(top - got, np.float64)
    return [gap[j, :m] for j, m in enumerate(counts)]


# ---------------------------------------------------------------- family
# What the harness runs for a configuration whose ``reference`` is this
# module (``bench/program.py`` lists the names it takes).

# the program's named scopes in this family's programs, innermost last
# where they nest (``kv_update`` in ``attention``, ``experts`` in ``moe``)
SCOPES = ("embed", "attention", "kv_update", "mlp", "moe", "experts",
          "lm_head")
PATTERN = (("mla", "moe"),)
# program name -> reference name, per sub-tree of one layer
MIX = {"ln": "attn_norm", "wq": "wq", "wkv_a": "wkv_a", "kv_ln": "kv_norm",
       "wkv_b": "wkv_b", "wo": "wo"}
MLP = {"ln": "mlp_norm", "w_gate": "w_gate", "w_up": "w_up",
       "w_down": "w_down"}
MOE = {"ln": "mlp_norm", "router": "router", "router_bias": "router_bias",
       "w_gate": "expert_gate", "w_up": "expert_up",
       "w_down": "expert_down"}
SHARED = {"w_gate": "shared_gate", "w_up": "shared_up",
          "w_down": "shared_down"}
TOP = {"embed": "embed", "final_ln": "final_norm", "lm_head": "lm_head"}
SERVE_ONLY = (f"{__name__} describes a serve-only family: no training "
              f"reference, optimizer or training count")


def arch_config(c: dict, cfg, reduced: bool):
    """The program's configuration ``cfg`` for the file ``c``: the RoPE
    base and RMSNorm epsilon the file states and, at full size, the share
    of routed experts it holds. Refuses a program configuration of another
    family or block pattern, and (at full size) one whose sizes disagree
    with the file."""
    if (cfg.family != "moe" or tuple(cfg.pattern) != PATTERN
            or cfg.n_dense_lead < 1 or cfg.moe_impl != "dropless"):
        raise ValueError(
            f"{c['name']}: the program runs {c['arch']} as family "
            f"{cfg.family!r} with pattern {cfg.pattern}; {__name__} "
            f"describes only latent attention with leading dense layers "
            f"and sigmoid-routed dropless experts {PATTERN}")
    cfg = cfg.with_(rope_theta=float(c["rope_theta"]),
                    rms_eps=float(c["rms_norm_eps"]))
    if not reduced:
        cfg = cfg.with_(n_held_experts=c["n_routed_experts"],
                        first_expert=c["first_routed_expert"])
        spec = MlaMoe.from_config(c)
        if c["q_lora_rank"] is not None or c["scoring_func"] != "sigmoid" \
                or c["topk_method"] != "noaux_tc" or c["n_group"] != 1 \
                or c["tie_word_embeddings"]:
            raise ValueError(f"{c['name']}: {__name__} describes no query "
                             f"latent, one routing group and an untied head")
        got = spec_of(c, cfg)
        if got != spec:
            raise ValueError(f"{c['arch']}: the program runs {got}, the "
                             f"configuration file states {spec}")
    return cfg


def spec_of(c: dict, cfg) -> MlaMoe:
    """The reference's description of the model the program runs (its
    router always normalises the top-k weights)."""
    return MlaMoe(vocab=cfg.vocab, d_model=cfg.d_model,
                  n_layers=cfg.n_layers, n_dense=cfg.n_dense_lead,
                  n_heads=cfg.n_heads, kv_lora=cfg.kv_lora_rank,
                  nope=cfg.qk_nope_head_dim, rope=cfg.qk_rope_head_dim,
                  v_dim=cfg.v_head_dim, d_ff=cfg.d_ff,
                  d_expert=cfg.expert_d_ff, n_experts=cfg.n_experts,
                  n_shared=cfg.n_shared_experts, top_k=cfg.moe_top_k,
                  routed_scale=float(cfg.routed_scale),
                  norm_topk=True, held=cfg.held_experts,
                  first=cfg.first_expert, rope_theta=float(cfg.rope_theta),
                  rms_eps=float(cfg.rms_eps))


def to_program(ref: dict) -> dict:
    """Reference layout -> the program's tree."""
    def mix(lay):
        return {p: lay[r] for p, r in MIX.items()}

    lead, lay = ref["lead"], ref["layers"]
    ffn = {p: lay[r] for p, r in MOE.items()}
    ffn["shared"] = {p: lay[r] for p, r in SHARED.items()}
    return {**{p: ref[r] for p, r in TOP.items()},
            "lead": ({"mix": mix(lead),
                      "ffn": {p: lead[r] for p, r in MLP.items()}},),
            "layers": ({"mix": mix(lay), "ffn": ffn},)}


def to_reference(prog: dict) -> dict:
    """The program's tree -> reference layout."""
    lead, blk = prog["lead"][0], prog["layers"][0]
    lay = {r: blk["mix"][p] for p, r in MIX.items()}
    lay.update({r: blk["ffn"][p] for p, r in MOE.items()})
    lay.update({r: blk["ffn"]["shared"][p] for p, r in SHARED.items()})
    first = {r: lead["mix"][p] for p, r in MIX.items()}
    first.update({r: lead["ffn"][p] for p, r in MLP.items()})
    return {**{r: prog[p] for p, r in TOP.items()}, "lead": first,
            "layers": lay}


def train_reference(*args, **kwargs):
    raise ValueError(SERVE_ONLY)


def leaf_norms(*args, **kwargs):
    raise ValueError(SERVE_ONLY)


def AdamW(*args, **kwargs):  # noqa: N802 (the family's name for it)
    raise ValueError(SERVE_ONLY)


def train_flops_per_token(*args, **kwargs):
    raise ValueError(SERVE_ONLY)


# Operations and bytes each algorithm needs, from a configuration file's
# shapes: the benchmark's own counts, never the program's
# ``cost_analysis``. A matrix product of (m, k) by (k, n) is 2mkn
# operations. Attention per query token and layer over c positions: the
# scores and the weighted values, 2 * heads * (qk + v) * c operations as
# the reference computes them. Routed experts: where the program's
# counters give the (token, expert) pairs computed or the held experts hit,
# the counts take them (``pairs``, ``experts_hit``); where a count is
# called without them it counts the most the share can compute: every held
# expert's weights once, and top_k pairs per token (at most ``held``).

BYTES = {"bfloat16": 2, "float32": 4}


def attn_params(d: MlaMoe) -> int:
    return sum(int(np.prod(s)) for n, s in d.attn_shapes().items()
               if n.startswith("w"))


def expert_params(d: MlaMoe) -> int:
    """One routed expert's weights."""
    return 3 * d.d_model * d.d_expert


def dense_params(d: MlaMoe) -> int:
    return 3 * d.d_model * d.d_ff


def moe_fixed_params(d: MlaMoe) -> int:
    """A MoE layer's product weights outside the routed experts: the
    router and the shared experts."""
    return d.d_model * d.n_experts + 3 * d.d_model * d.n_shared * d.d_expert


def _flops(d: MlaMoe, tokens: int, context_sum: int, pairs) -> int:
    """``pairs``: the counter's pairs summed over the MoE layers, or None
    for the most the share computes."""
    n_moe = d.n_layers - d.n_dense
    per_token = (d.n_layers * attn_params(d) + d.n_dense * dense_params(d)
                 + n_moe * moe_fixed_params(d))
    if pairs is None:
        pairs = n_moe * tokens * min(d.top_k, d.held)
    attn = d.n_layers * 2 * d.n_heads * (d.qk_dim + d.v_dim) * context_sum
    return 2 * tokens * per_token + 2 * pairs * expert_params(d) + attn


def prefill_flops(c: dict, s: int, pairs=None) -> int:
    """A prompt of ``s`` tokens: every layer over every token, causal
    attention, and the output head at the last position only."""
    d = MlaMoe.from_config(c)
    return _flops(d, s, s * (s + 1) // 2, pairs) + 2 * d.d_model * d.vocab


def decode_flops(c: dict, tokens: int, context: int, pairs=None) -> int:
    """One decode step of ``tokens`` sequences whose contexts (the new token
    included) add up to ``context`` positions, as the reference computes
    attention (per head, keys and values expanded)."""
    d = MlaMoe.from_config(c)
    return _flops(d, tokens, context, pairs) + 2 * tokens * d.d_model * d.vocab


def latent_bytes(c: dict) -> int:
    """One position's latent cache over every layer: the latent and the
    roped shared key."""
    d = MlaMoe.from_config(c)
    return d.n_layers * (d.kv_lora + d.rope) * BYTES[c["compute_dtype"]]


def decode_scope_bytes(c: dict, scope: str, tokens: int, context: int,
                       experts_hit=None) -> int:
    """Bytes one decode step needs in a named scope of the program:
    ``attention``, the latent positions attended read once and the
    attention weights and norms of every layer (the new positions' writes
    are ``kv_update``'s); ``experts``, the weights of the held experts hit
    (the counter summed over the layers, or every held expert of every
    layer)."""
    d = MlaMoe.from_config(c)
    wb = BYTES[c["param_dtype"]]
    if scope == "attention":
        weights = d.n_layers * sum(int(np.prod(s)) for n, s in
                                   d.attn_shapes().items()
                                   if n != "mlp_norm")
        return latent_bytes(c) * context + weights * wb
    if scope == "experts":
        hit = (experts_hit if experts_hit is not None
               else (d.n_layers - d.n_dense) * d.held)
        return hit * expert_params(d) * wb
    raise ValueError(f"no byte count for scope {scope!r}")


def decode_bytes(c: dict, tokens: int, context: int) -> int:
    """One decode step: every weight but the embedding once (every held
    expert, no counter being given), one embedding row per token, the
    latent cache of ``context`` positions read and of ``tokens`` positions
    written, and the float32 logits written."""
    d = MlaMoe.from_config(c)
    wb = BYTES[c["param_dtype"]]
    n_moe = d.n_layers - d.n_dense
    norms = d.n_layers * (2 * d.d_model + d.kv_lora) + d.d_model
    weights = (d.n_layers * attn_params(d) + d.n_dense * dense_params(d)
               + n_moe * (3 * d.d_model * d.n_shared * d.d_expert
                          + d.held * expert_params(d))
               + d.d_model * d.vocab + norms)
    router = n_moe * (d.d_model + 1) * d.n_experts * 4      # float32
    return (weights * wb + router + tokens * d.d_model * wb
            + latent_bytes(c) * (context + tokens) + tokens * d.vocab * 4)
