"""Unified block-stack LM covering all 10 assigned architectures.

An architecture is a *period pattern* of (mixer, ffn) pairs — e.g. jamba is
period 8: one attention layer, seven mamba layers, MoE on every other FFN.
The layer stack is ``lax.scan`` over period repeats with weights stacked on a
leading repeat axis, so HLO size is O(period), not O(n_layers) — essential
for compiling 72-layer models against a 512-device mesh.

Mixers:  attn | swa | mla | mamba | mlstm | slstm | none
FFNs:    mlp  | moe | gelu  | none

``n_dense_lead`` layers of (the pattern's first mixer, ``mlp``) may run
ahead of the scanned pattern, as their own stack (``params["lead"]``,
``cache["lead"]``), e.g. DeepSeek-V3's leading dense layers.

Three entry points (built by ``repro.train.steps``):
  train:   tokens -> chunked-softmax xent loss (never materializes B,S,V)
  prefill: tokens -> logits for the last position + a decode cache
  decode:  one token + cache -> next-token logits + updated cache; the
           layer scan carries the stacked cache, and each layer writes its
           token in place and reads its K/V by index
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM

Params = Dict[str, Any]

# ---------------------------------------------------------------------------
# Activation sharding constraints.  GSPMD left alone resolves the
# FSDP-weight vs batch-sharded-activation einsum conflict by all-gathering
# the *batch* (catastrophic).  Step builders register the batch mesh axes
# here; the stack re-constrains x at every block boundary so the batch stays
# sharded and XLA all-gathers the (much smaller) per-layer weights instead.

_BATCH_AXES: Optional[Tuple[str, ...]] = None
_SEQ_AXIS: Optional[str] = None
_SEQ_DIVISOR: int = 1


def set_batch_axes(axes, seq_axis: Optional[str] = None,
                   seq_divisor: int = 1) -> None:
    """``seq_axis``: sequence-parallel residual stream (Megatron-SP style) —
    norms/elementwise run seq-sharded and the per-layer TP all-reduce of the
    (B,S,D) stream becomes a cheaper gather/scatter pair."""
    global _BATCH_AXES, _SEQ_AXIS, _SEQ_DIVISOR
    _BATCH_AXES = axes
    _SEQ_AXIS = seq_axis
    _SEQ_DIVISOR = max(seq_divisor, 1)


def constrain_batch(x: jnp.ndarray) -> jnp.ndarray:
    if _BATCH_AXES is None and _SEQ_AXIS is None:
        return x
    spec = [None] * x.ndim
    spec[0] = _BATCH_AXES
    if (_SEQ_AXIS is not None and x.ndim == 3
            and x.shape[1] % _SEQ_DIVISOR == 0 and x.shape[1] > 1):
        spec[1] = _SEQ_AXIS
    try:
        return jax.lax.with_sharding_constraint(x, P(*spec))
    except (ValueError, RuntimeError):  # no mesh context (plain CPU tests)
        return x


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                 # dense|moe|ssm|hybrid|vlm|audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    pattern: Tuple[Tuple[str, str], ...] = (("attn", "mlp"),)
    # attention
    qkv_bias: bool = False
    swa_window: Optional[int] = None
    use_rope: bool = True
    rope_theta: float = 10000.0
    attn_chunk: int = 1024
    rms_eps: float = 1e-6
    # multi-head latent attention (mixer "mla"): per head a query and key
    # of qk_nope + qk_rope dims and a value of v_head_dim, expanded from a
    # kv_lora_rank-wide latent (the cache holds it and the roped key)
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # layers of (pattern[0] mixer, "mlp" of d_ff) ahead of the pattern
    n_dense_lead: int = 0
    # moe
    n_experts: int = 0          # the router's width: every expert
    moe_top_k: int = 0
    moe_capacity_factor: float = 1.25
    moe_group_size: int = 2048
    moe_impl: str = "dropping"
    aux_loss_weight: float = 0.01
    moe_d_ff: int = 0           # expert width (0: d_ff)
    # "dropless" only (sigmoid + selection-bias routing, repro.models.moe):
    n_shared_experts: int = 0   # shared experts, one SwiGLU of n x moe width
    routed_scale: float = 1.0   # the normalised top-k weights times this
    n_held_experts: int = 0     # experts this chip holds (0: all)
    first_expert: int = 0       # global id of the first held expert
    # ssm
    ssm_chunk: int = 64
    d_state: int = 16
    # structure
    enc_dec: bool = False
    n_enc_layers: int = 0
    enc_seq: int = 0            # audio frames fed by the frontend stub
    vision_prefix: int = 0      # VLM patch embeddings fed by the stub
    mlp_variant: str = "swiglu"
    # numerics / memory
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    remat: bool = True
    loss_chunk: int = 512

    def __post_init__(self):
        if self.moe_impl != "dropless" and (
                self.n_shared_experts or self.routed_scale != 1.0
                or self.n_held_experts or self.first_expert):
            raise ValueError(
                f"{self.name}: shared experts, routed_scale and a held "
                f"share of the experts need moe_impl='dropless', not "
                f"{self.moe_impl!r}")

    # long-context support marker (sub-quadratic mixers or SWA)
    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def period(self) -> int:
        return len(self.pattern)

    @property
    def repeats(self) -> int:
        body = self.n_layers - self.n_dense_lead
        assert body % self.period == 0, (self.n_layers, self.period)
        return body // self.period

    @property
    def lead_pattern(self) -> Tuple[Tuple[str, str], ...]:
        return ((self.pattern[0][0], "mlp"),)

    @property
    def layer_kinds(self) -> Tuple[Tuple[str, str], ...]:
        """(mixer, ffn) of every layer, in order."""
        return (self.lead_pattern * self.n_dense_lead
                + tuple(self.pattern) * self.repeats)

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def held_experts(self) -> int:
        return self.n_held_experts or self.n_experts

    @property
    def counts_moe(self) -> bool:
        """Whether the MoE layers count (:mod:`repro.models.moe`
        ``COUNTERS``); prefill and decode then hand the counters back."""
        return self.moe_impl == "dropless" and any(
            f == "moe" for _, f in self.pattern)

    @property
    def sub_quadratic(self) -> bool:
        mixers = {m for m, _ in self.pattern}
        return bool(mixers & {"mamba", "mlstm", "slstm"}) or (
            "attn" not in mixers and "swa" in mixers
            and self.swa_window is not None)

    def with_(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)


# --------------------------------------------------------------------- init

def _init_one_layer(rng, cfg: ArchConfig, mixer: str, ffn: str,
                    cross: bool) -> Params:
    rs = jax.random.split(rng, 3)
    p: Params = {}
    dt = cfg.param_dtype
    if mixer in ("attn", "swa"):
        p["mix"] = L.init_attention(rs[0], cfg.d_model, cfg.n_heads,
                                    cfg.n_kv_heads, cfg.head_dim,
                                    cfg.qkv_bias, dt)
    elif mixer == "mla":
        p["mix"] = L.init_mla(rs[0], cfg.d_model, cfg.n_heads,
                              cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                              cfg.qk_rope_head_dim, cfg.v_head_dim, dt)
    elif mixer == "mamba":
        p["mix"] = SSM.init_mamba(rs[0], cfg.d_model, cfg.d_state, dtype=dt)
    elif mixer == "mlstm":
        p["mix"] = SSM.init_mlstm(rs[0], cfg.d_model, cfg.n_heads, dt)
    elif mixer == "slstm":
        p["mix"] = SSM.init_slstm(rs[0], cfg.d_model, cfg.n_heads, dt)
    if cross:
        p["cross"] = L.init_attention(rs[2], cfg.d_model, cfg.n_heads,
                                      cfg.n_kv_heads, cfg.head_dim, False, dt)
    if ffn == "moe":
        p["ffn"] = MOE.init_moe(rs[1], cfg.d_model, cfg.expert_d_ff,
                                cfg.held_experts, dt, n_router=cfg.n_experts,
                                n_shared=cfg.n_shared_experts,
                                router_bias=cfg.moe_impl == "dropless")
    elif ffn in ("mlp", "gelu"):
        variant = "swiglu" if ffn == "mlp" else "gelu"
        p["ffn"] = L.init_mlp(rs[1], cfg.d_model, cfg.d_ff, variant, dt)
    return p


def _init_stack(rng, cfg: ArchConfig, n_layers: int, cross: bool,
                pattern=None) -> Tuple[Params, ...]:
    """Stacked params per period position: tuple_p of pytrees (R, ...)."""
    pattern = pattern or cfg.pattern
    repeats = n_layers // len(pattern)
    out = []
    for pidx, (mixer, ffn) in enumerate(pattern):
        keys = jax.random.split(jax.random.fold_in(rng, pidx), repeats)
        out.append(jax.vmap(
            lambda k: _init_one_layer(k, cfg, mixer, ffn, cross))(keys))
    return tuple(out)


def init_params(rng, cfg: ArchConfig) -> Params:
    rs = jax.random.split(rng, 5)
    dt = cfg.param_dtype
    scale = 1.0 / math.sqrt(cfg.d_model)
    params: Params = {
        "embed": (jax.random.normal(rs[0], (cfg.vocab, cfg.d_model),
                                    jnp.float32) * scale).astype(dt),
        "final_ln": jnp.ones((cfg.d_model,), dt),
        "lm_head": (jax.random.normal(rs[1], (cfg.d_model, cfg.vocab),
                                      jnp.float32) * scale).astype(dt),
        "layers": _init_stack(rs[2], cfg, cfg.n_layers - cfg.n_dense_lead,
                              cross=cfg.enc_dec),
    }
    if cfg.n_dense_lead:
        params["lead"] = _init_stack(rs[4], cfg, cfg.n_dense_lead,
                                     cross=False, pattern=cfg.lead_pattern)
    if cfg.enc_dec:
        enc_cfg = cfg.with_(pattern=(("attn", "gelu"),))
        params["enc_layers"] = _init_stack(rs[3], enc_cfg, cfg.n_enc_layers,
                                           cross=False)
        params["enc_ln"] = jnp.ones((cfg.d_model,), dt)
    return params


def param_count(params: Params) -> int:
    return sum(int(jnp.size(l)) for l in jax.tree.leaves(params))


def abstract_params(rng, cfg: ArchConfig) -> Params:
    """ShapeDtypeStruct tree — dry-run init without allocation."""
    return jax.eval_shape(lambda r: init_params(r, cfg), rng)


# ------------------------------------------------------------------- blocks

def _apply_block(x, p, cfg: ArchConfig, mixer: str, ffn: str,
                 positions, causal: bool,
                 enc_kv=None):
    """Training/prefill block. Returns (x, aux, cache_entry, counters or
    None)."""
    aux = jnp.zeros((), jnp.float32)
    counts = None
    cache: Dict[str, Any] = {}
    if mixer in ("attn", "swa"):
        with jax.named_scope("attention"):
            window = cfg.swa_window if mixer == "swa" else None
            b, s, _ = x.shape
            h = L.rmsnorm(x, p["mix"]["ln"], cfg.rms_eps)
            q = L.dense(h, p["mix"]["wq"], p["mix"].get("bq")) \
                .reshape(b, s, cfg.n_heads, cfg.head_dim)
            k = L.dense(h, p["mix"]["wk"], p["mix"].get("bk")) \
                .reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
            v = L.dense(h, p["mix"]["wv"], p["mix"].get("bv")) \
                .reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
            if cfg.use_rope:
                q = L.rope(q, positions, cfg.rope_theta)
                k = L.rope(k, positions, cfg.rope_theta)
            out = L.chunked_attention(q, k, v, causal=causal, window=window,
                                      chunk=cfg.attn_chunk)
            x = x + L.dense(out.reshape(b, s, -1), p["mix"]["wo"])
            cache["k"], cache["v"] = k, v
    elif mixer == "mla":
        with jax.named_scope("attention"):
            b, s, _ = x.shape
            h = L.rmsnorm(x, p["mix"]["ln"], cfg.rms_eps)
            q, c, k_pe = L.mla_project(h, p["mix"], cfg, positions)
            k, v = L.mla_expand(c, k_pe, p["mix"], cfg)
            out = L.chunked_attention(q, k, v, causal=causal,
                                      chunk=cfg.attn_chunk)
            x = x + L.dense(out.reshape(b, s, -1), p["mix"]["wo"])
            cache["c"], cache["kr"] = c, k_pe
    elif mixer == "mamba":
        x, st = SSM.mamba_block(x, p["mix"], cfg)
        cache["ssm"] = st
    elif mixer == "mlstm":
        x, st = SSM.mlstm_block(x, p["mix"], cfg)
        cache["lstm"] = st
    elif mixer == "slstm":
        x, st = SSM.slstm_block(x, p["mix"], cfg)
        cache["slstm"] = st

    if enc_kv is not None and "cross" in p:
        x = L.attention_block(x, p["cross"], cfg, positions, causal=False,
                              cross_kv=enc_kv)

    if ffn == "moe":
        with jax.named_scope("moe"):
            x, aux, counts = MOE.moe_block(x, p["ffn"], cfg)
    elif ffn in ("mlp", "gelu"):
        with jax.named_scope("mlp"):
            x = L.mlp(x, p["ffn"], "swiglu" if ffn == "mlp" else "gelu",
                      cfg.rms_eps)
    return x, aux, cache, counts


def _counts0(cfg: ArchConfig, pattern):
    """The counters' initial sum, or None where no layer counts."""
    if cfg.counts_moe and any(f == "moe" for _, f in pattern):
        return jnp.zeros((len(MOE.COUNTERS),), jnp.int32)
    return None


def _run_stack(x, stack, cfg: ArchConfig, pattern, positions, causal,
               enc_out=None, collect_cache: bool = False):
    """Scan over period repeats. Returns (x, aux_total, caches per pos,
    counters summed over the layers or None)."""

    def one_block(x, p, positions, enc_kv, mixer, ffn):
        x = constrain_batch(_grad_cast(x))
        x, aux_i, cache, n_i = _apply_block(x, p, cfg, mixer, ffn,
                                            positions, causal, enc_kv)
        return constrain_batch(x), aux_i, cache, n_i

    if cfg.remat:
        # nested remat: backward re-materializes one block at a time, so the
        # peak holds a single block's internals, not the whole period's
        block_fns = {
            (mixer, ffn): jax.checkpoint(
                partial(one_block, mixer=mixer, ffn=ffn),
                static_argnums=())
            for mixer, ffn in set(pattern)}
    else:
        block_fns = {(mixer, ffn): partial(one_block, mixer=mixer, ffn=ffn)
                     for mixer, ffn in set(pattern)}

    def period_body(carry, layer_params):
        x, aux, n = carry
        caches = []
        for pidx, (mixer, ffn) in enumerate(pattern):
            p = layer_params[pidx]
            enc_kv = None
            if enc_out is not None and "cross" in p:
                b, f, _ = enc_out.shape
                k_enc = L.dense(enc_out, p["cross"]["wk"]) \
                    .reshape(b, f, cfg.n_kv_heads, cfg.head_dim)
                v_enc = L.dense(enc_out, p["cross"]["wv"]) \
                    .reshape(b, f, cfg.n_kv_heads, cfg.head_dim)
                enc_kv = (k_enc, v_enc)
                caches_entry_extra = {"xk": k_enc, "xv": v_enc}
            x, aux_i, cache, n_i = block_fns[(mixer, ffn)](
                x, p, positions, enc_kv)
            if enc_out is not None and "cross" in p:
                cache.update(caches_entry_extra)
            aux = aux + aux_i
            if n_i is not None:
                n = n + n_i
            caches.append(cache)
        return (x, aux, n), tuple(caches) if collect_cache else None

    # outer remat: the scan saves only the period-boundary carry; inner
    # per-block remat (above) keeps the period backward to one block's
    # internals at a time.
    body = jax.checkpoint(period_body) if cfg.remat else period_body
    (x, aux, n), caches = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32), _counts0(cfg, pattern)), stack)
    return x, aux, caches, n


# ------------------------------------------------------------------ forward

def embed_inputs(params: Params, batch: Dict[str, jnp.ndarray],
                 cfg: ArchConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Token/frontend embedding. Returns (x (B,S,D), positions (B,S))."""
    tokens = batch["tokens"]
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    if cfg.vision_prefix:
        patches = batch["patches"].astype(cfg.dtype)   # (B, P, D) stub
        x = jnp.concatenate([patches, x], axis=1)
    x = constrain_batch(x)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    return x, positions


def encode(params: Params, batch: Dict[str, jnp.ndarray],
           cfg: ArchConfig) -> jnp.ndarray:
    """Whisper-style encoder over precomputed frames (frontend stub)."""
    frames = batch["frames"].astype(cfg.dtype)          # (B, F, D)
    b, f, _ = frames.shape
    x = frames + L.sinusoidal_positions(f, cfg.d_model, cfg.dtype)
    pos = jnp.broadcast_to(jnp.arange(f), (b, f))
    enc_cfg = cfg.with_(pattern=(("attn", "gelu"),), use_rope=False)
    x, _, _, _ = _run_stack(x, params["enc_layers"], enc_cfg,
                            enc_cfg.pattern, pos, causal=False)
    return L.rmsnorm(x, params["enc_ln"], cfg.rms_eps)


def _forward(params: Params, batch: Dict[str, jnp.ndarray],
             cfg: ArchConfig, collect_cache: bool = False):
    """Full forward to final hidden states. Returns (h, aux, caches of
    the pattern, caches of the leading layers, counters, enc)."""
    x, positions = embed_inputs(params, batch, cfg)
    enc_out = encode(params, batch, cfg) if cfg.enc_dec else None
    lead = None
    if cfg.n_dense_lead:
        x, _, lead, _ = _run_stack(x, params["lead"], cfg, cfg.lead_pattern,
                                   positions, causal=True,
                                   collect_cache=collect_cache)
    x, aux, caches, counts = _run_stack(
        x, params["layers"], cfg, cfg.pattern, positions, causal=True,
        enc_out=enc_out, collect_cache=collect_cache)
    with jax.named_scope("lm_head"):
        h = L.rmsnorm(x, params["final_ln"], cfg.rms_eps)
    return h, aux, caches, lead, counts, enc_out


def hidden_states(params: Params, batch: Dict[str, jnp.ndarray],
                  cfg: ArchConfig, collect_cache: bool = False):
    """Full forward to final hidden states. Returns (h, aux, caches, enc)."""
    h, aux, caches, _, _, enc_out = _forward(params, batch, cfg,
                                             collect_cache)
    return h, aux, caches, enc_out


@jax.custom_vjp
def _grad_cast(x):
    """Identity; casts the cotangent back to x.dtype.  Without this the f32
    loss math promotes the entire backward residual stream to f32 (2x
    activation-grad memory and bandwidth)."""
    return x


def _grad_cast_fwd(x):
    return x, jnp.zeros((0,), x.dtype)  # dtype token (residuals must be jax types)


def _grad_cast_bwd(token, g):
    return (g.astype(token.dtype),)


_grad_cast.defvjp(_grad_cast_fwd, _grad_cast_bwd)


def chunked_xent(h: jnp.ndarray, lm_head: jnp.ndarray,
                 labels: jnp.ndarray, chunk: int) -> Tuple[jnp.ndarray,
                                                           jnp.ndarray]:
    """Cross entropy over seq chunks — never materializes (B, S, V).

    labels < 0 are masked. Returns (sum_nll, n_tokens).
    """
    b, s, d = h.shape
    chunk = min(chunk, s)
    n_chunks = -(-s // chunk)
    pad = n_chunks * chunk - s
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    hc = h.reshape(b, n_chunks, chunk, d).swapaxes(0, 1)
    lc = labels.reshape(b, n_chunks, chunk).swapaxes(0, 1)

    def step(carry, inp):
        nll, cnt = carry
        hi, li = inp
        hi = constrain_batch(hi)
        logits = jnp.einsum("bsd,dv->bsv", hi,
                            lm_head.astype(hi.dtype)).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(li, 0)[..., None], axis=-1)[..., 0]
        mask = (li >= 0).astype(jnp.float32)
        nll = nll + jnp.sum((lse - gold) * mask)
        cnt = cnt + jnp.sum(mask)
        return (nll, cnt), None

    body = jax.checkpoint(step)
    (nll, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hc, lc))
    return nll, cnt


def loss_fn(params: Params, batch: Dict[str, jnp.ndarray],
            cfg: ArchConfig) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    h, aux, _, _ = hidden_states(params, batch, cfg)
    h = _grad_cast(h)
    labels = batch["labels"]
    if cfg.vision_prefix:  # loss only over the text segment
        b = labels.shape[0]
        pad = jnp.full((b, cfg.vision_prefix), -1, labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)
    with jax.named_scope("lm_head"):
        nll, cnt = chunked_xent(h, params["lm_head"], labels,
                                cfg.loss_chunk)
    loss = nll / jnp.maximum(cnt, 1.0)
    total = loss + cfg.aux_loss_weight * aux / max(cfg.n_layers, 1)
    return total, {"nll": loss, "aux": aux, "tokens": cnt}


def logits_last(params: Params, h: jnp.ndarray, cfg: ArchConfig
                ) -> jnp.ndarray:
    """Logits for the last position only. h: (B, S, D) -> (B, V)."""
    return jnp.einsum("bd,dv->bv", h[:, -1],
                      params["lm_head"].astype(h.dtype)).astype(jnp.float32)


# ------------------------------------------------------------------- decode

def _cache_seq_len(cfg: ArchConfig, mixer: str, max_len: int) -> int:
    """SWA layers keep a ring buffer of ``window`` tokens, never more."""
    if mixer == "swa" and cfg.swa_window is not None:
        return min(max_len, cfg.swa_window)
    return max_len


def _cache_entries(cfg: ArchConfig, pattern, r: int, batch: int,
                   max_len: int) -> Tuple[Params, ...]:
    """Zero cache entries of one stack: per period position, stacked over
    its ``r`` repeats."""
    dt = cfg.dtype
    layers = []
    for mixer, _ in pattern:
        entry: Dict[str, Any] = {}
        if mixer in ("attn", "swa"):
            c = _cache_seq_len(cfg, mixer, max_len)
            kv = (r, batch, c, cfg.n_kv_heads, cfg.head_dim)
            entry["k"] = jnp.zeros(kv, dt)
            entry["v"] = jnp.zeros(kv, dt)
        elif mixer == "mla":
            entry["c"] = jnp.zeros((r, batch, max_len, cfg.kv_lora_rank), dt)
            entry["kr"] = jnp.zeros((r, batch, max_len,
                                     cfg.qk_rope_head_dim), dt)
        elif mixer == "mamba":
            st = SSM.init_mamba_state(
                batch, jax.tree.map(lambda x: x[0],
                                    _dummy_mamba_params(cfg)))
            entry["ssm"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (r, *x.shape)), st)
        elif mixer == "mlstm":
            st = SSM.init_mlstm_state(batch, cfg.n_heads, cfg.head_dim)
            entry["lstm"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (r, *x.shape)), st)
        elif mixer == "slstm":
            st = SSM.init_slstm_state(batch, cfg.d_model)
            entry["slstm"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (r, *x.shape)), st)
        if cfg.enc_dec:
            kv = (r, batch, cfg.enc_seq, cfg.n_kv_heads, cfg.head_dim)
            entry["xk"] = jnp.zeros(kv, dt)
            entry["xv"] = jnp.zeros(kv, dt)
        layers.append(entry)
    return tuple(layers)


def init_cache(cfg: ArchConfig, batch: int, max_len: int) -> Params:
    """Zero decode cache: per period position, stacked over repeats (the
    leading layers' under ``lead``). Every layer leaf is (R, B, ...)."""
    # per-sequence positions: each batch slot may be at a different depth
    cache = {"pos": jnp.zeros((batch,), jnp.int32),
             "layers": _cache_entries(cfg, cfg.pattern, cfg.repeats, batch,
                                      max_len)}
    if cfg.n_dense_lead:
        cache["lead"] = _cache_entries(cfg, cfg.lead_pattern,
                                       cfg.n_dense_lead, batch, max_len)
    return cache


def _dummy_mamba_params(cfg: ArchConfig):
    di = 2 * cfg.d_model
    return {"in_proj": jnp.zeros((1, cfg.d_model, 2 * di), cfg.dtype),
            "A_log": jnp.zeros((1, di, cfg.d_state), jnp.float32),
            "conv_w": jnp.zeros((1, 4, di), cfg.dtype)}


def _decode_block(x, p, cfg: ArchConfig, mixer: str, ffn: str,
                  entry, i, pos):
    """One-token block of layer ``i``. x: (B,1,D); ``entry``: this period
    position's cache, stacked over repeats. Returns (x, updated entry,
    counters or None)."""
    new = dict(entry)
    counts = None
    at = partial(jax.lax.dynamic_index_in_dim, index=i, keepdims=False)
    if mixer in ("attn", "swa"):
        with jax.named_scope("attention"):
            b = x.shape[0]
            window = cfg.swa_window if mixer == "swa" else None
            ring = (mixer == "swa" and cfg.swa_window is not None
                    and entry["k"].shape[2] <= cfg.swa_window)
            h = L.rmsnorm(x, p["mix"]["ln"], cfg.rms_eps)
            q = L.dense(h, p["mix"]["wq"], p["mix"].get("bq")) \
                .reshape(b, 1, cfg.n_heads, cfg.head_dim)
            k = L.dense(h, p["mix"]["wk"], p["mix"].get("bk")) \
                .reshape(b, 1, cfg.n_kv_heads, cfg.head_dim)
            v = L.dense(h, p["mix"]["wv"], p["mix"].get("bv")) \
                .reshape(b, 1, cfg.n_kv_heads, cfg.head_dim)
            if cfg.use_rope:
                pp = jnp.broadcast_to(jnp.reshape(pos, (-1, 1))
                                      if jnp.ndim(pos) else pos, (b, 1))
                q = L.rope(q, pp, cfg.rope_theta)
                k = L.rope(k, pp, cfg.rope_theta)
            with jax.named_scope("kv_update"):
                new["k"], new["v"] = L.update_kv_cache(
                    entry["k"], entry["v"], k, v, i, pos, ring=ring)
            # the layer's K/V read from the written leaves, so they hold
            # this token; XLA fuses the slice into the attention reads
            kc, vc = at(new["k"]), at(new["v"])
            if ring:
                out = L.decode_attention_ring(q, kc, vc, pos, cfg.swa_window)
            else:
                out = L.decode_attention(q, kc, vc, pos + 1, window=window)
            x = x + L.dense(out.reshape(b, 1, -1), p["mix"]["wo"])
    elif mixer == "mla":
        with jax.named_scope("attention"):
            b = x.shape[0]
            pp = jnp.broadcast_to(jnp.reshape(pos, (-1, 1))
                                  if jnp.ndim(pos) else pos, (b, 1))
            h = L.rmsnorm(x, p["mix"]["ln"], cfg.rms_eps)
            q, c, k_pe = L.mla_project(h, p["mix"], cfg, pp)
            with jax.named_scope("kv_update"):
                new["c"], new["kr"] = L.update_kv_cache(
                    entry["c"], entry["kr"], c, k_pe, i, pos)
            out = L.mla_decode_attention(q, at(new["c"]), at(new["kr"]),
                                         p["mix"]["wkv_b"], pos + 1, cfg)
            x = x + L.dense(out.reshape(b, 1, -1), p["mix"]["wo"])
    elif mixer in ("mamba", "mlstm", "slstm"):
        key, state, block = {
            "mamba": ("ssm", SSM.MambaState, SSM.mamba_block),
            "mlstm": ("lstm", SSM.LstmState, SSM.mlstm_block),
            "slstm": ("slstm", SSM.SlstmState, SSM.slstm_block)}[mixer]
        x, st = block(x, p["mix"], cfg, state(*map(at, entry[key])),
                      decode=True)
        # recurrent states are small: each is written back whole
        new[key] = jax.tree.map(lambda s, n: s.at[i].set(n), entry[key], st)

    if cfg.enc_dec and "cross" in p:
        b = x.shape[0]
        h = L.rmsnorm(x, p["cross"]["ln"], cfg.rms_eps)
        q = L.dense(h, p["cross"]["wq"]) \
            .reshape(b, 1, cfg.n_heads, cfg.head_dim)
        out = L.decode_attention(q, at(entry["xk"]), at(entry["xv"]),
                                 jnp.asarray(cfg.enc_seq, jnp.int32))
        x = x + L.dense(out.reshape(b, 1, -1), p["cross"]["wo"])

    if ffn == "moe":
        with jax.named_scope("moe"):
            x, _, counts = MOE.moe_block(x, p["ffn"], cfg)
    elif ffn in ("mlp", "gelu"):
        with jax.named_scope("mlp"):
            x = L.mlp(x, p["ffn"], "swiglu" if ffn == "mlp" else "gelu",
                      cfg.rms_eps)
    return x, new, counts


def _decode_stack(x, stack, entries, cfg: ArchConfig, pattern, pos):
    """The one-token blocks of one stack. Returns (x, its new entries,
    counters summed over its layers or None)."""

    def body(carry, xs):
        x, entries, n = carry
        layer_params, i = xs
        new_entries = []
        for pidx, (mixer, ffn) in enumerate(pattern):
            x, new, n_i = _decode_block(x, layer_params[pidx], cfg, mixer,
                                        ffn, entries[pidx], i, pos)
            if n_i is not None:
                n = n + n_i
            new_entries.append(new)
        return (x, tuple(new_entries), n), None

    # the stacked cache rides in the carry and each layer indexes it by
    # ``i``, so a donated cache is written in place, one token per slot,
    # and no layer's cache is sliced out or stacked back. The scan stays
    # unscoped.
    repeats = jax.tree.leaves(entries)[0].shape[0]
    (x, new, n), _ = jax.lax.scan(
        body, (x, entries, _counts0(cfg, pattern)),
        (stack, jnp.arange(repeats)))
    return x, new, n


def decode_step(params: Params, cache: Params, tokens: jnp.ndarray,
                cfg: ArchConfig):
    """One decode step. tokens: (B, 1) -> (logits (B, V), new cache), and
    where ``cfg.counts_moe`` the MoE counters summed over the layers (an
    int32 (3,), :data:`repro.models.moe.COUNTERS`)."""
    pos = cache["pos"]
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    new_cache = {}
    if cfg.n_dense_lead:
        x, new_cache["lead"], _ = _decode_stack(
            x, params["lead"], cache["lead"], cfg, cfg.lead_pattern, pos)
    x, new_layers, n = _decode_stack(x, params["layers"], cache["layers"],
                                     cfg, cfg.pattern, pos)
    with jax.named_scope("lm_head"):
        h = L.rmsnorm(x, params["final_ln"], cfg.rms_eps)
        logits = logits_last(params, h, cfg)
    new_cache.update({"pos": pos + 1, "layers": new_layers})
    if cfg.counts_moe:
        return logits, new_cache, n
    return logits, new_cache


def _prefill_entries(caches, cfg: ArchConfig, pattern, s: int,
                     max_len: int) -> Tuple[Params, ...]:
    """A stack's prefill caches ((R, B, S, ...) per leaf) padded to
    ``max_len``, or for a ring cache the last tokens, rotated."""
    layers = []
    for pidx, (mixer, _) in enumerate(pattern):
        entry = dict(caches[pidx]) if caches is not None else {}
        if mixer in ("attn", "swa"):
            with jax.named_scope("attention"), jax.named_scope("kv_update"):
                c = _cache_seq_len(cfg, mixer, max_len)
                k, v = entry.pop("k"), entry.pop("v")          # (R,B,S,KV,Dh)
                if c >= s:
                    padw = ((0, 0), (0, 0), (0, c - s), (0, 0), (0, 0))
                    entry["k"] = jnp.pad(k, padw)
                    entry["v"] = jnp.pad(v, padw)
                else:  # ring: keep the last c tokens, rotated so that
                       # slot (s % c) is the oldest (next write target)
                    k, v = k[:, :, s - c:], v[:, :, s - c:]
                    shift = s % c
                    idx = (jnp.arange(c) - shift) % c
                    entry["k"] = k[:, :, idx]
                    entry["v"] = v[:, :, idx]
        elif mixer == "mla":
            with jax.named_scope("attention"), jax.named_scope("kv_update"):
                padw = ((0, 0), (0, 0), (0, max_len - s), (0, 0))
                for name in ("c", "kr"):                       # (R,B,S,W)
                    entry[name] = jnp.pad(entry[name], padw)
        layers.append(entry)
    return tuple(layers)


def prefill(params: Params, batch: Dict[str, jnp.ndarray], cfg: ArchConfig,
            max_len: int):
    """Prefill: full forward, build a decode cache padded to ``max_len``.
    Returns (logits, cache), and the MoE counters as :func:`decode_step`
    gives them."""
    h, _, caches, lead, n, _ = _forward(params, batch, cfg,
                                        collect_cache=True)
    s = h.shape[1]
    cache = {}
    if cfg.n_dense_lead:
        cache["lead"] = _prefill_entries(lead, cfg, cfg.lead_pattern, s,
                                         max_len)
    layers = _prefill_entries(caches, cfg, cfg.pattern, s, max_len)
    with jax.named_scope("lm_head"):
        logits = logits_last(params, h, cfg)
    b = h.shape[0]
    cache.update({"pos": jnp.full((b,), s, jnp.int32), "layers": layers})
    if cfg.counts_moe:
        return logits, cache, n
    return logits, cache
