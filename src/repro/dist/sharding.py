"""Sharding rules: ArchConfig + Mesh + ShardingRules -> NamedSharding trees.

This module is the single place where parameter/optimizer/batch/cache
placement is decided.  Everything downstream (``repro.train.steps``, the
trainer, the dry-run estimator, the shard-space autotuner) consumes the
functional API here and never hand-writes a ``PartitionSpec``.

Layout policy (Megatron-style TP + optional ZeRO-3 + expert parallelism):

  * **Tensor parallel** (``rules.tp_axis``, default ``"model"``):
      - attention qkv projections are column-parallel (output features
        sharded), the output projection is row-parallel (contraction dim
        sharded) — the pair needs one all-reduce per block;
      - MLPs shard ``w_gate``/``w_up`` column-wise and ``w_down`` row-wise;
      - the embedding shards the *vocab* dim, the LM head its vocab output
        (the chunked-softmax loss reduces over the sharded vocab);
      - MoE FFNs prefer **expert parallelism** (experts split over the model
        axis); when ``n_experts`` does not divide the axis they fall back to
        per-expert tensor parallelism.
  * **Data parallel**: the batch dim of inputs/activations is sharded over
    every non-model mesh axis (``("pod", "data")`` on a multi-pod mesh).
  * **FSDP** (``rules.fsdp_weights``): each large parameter additionally
    shards one remaining unsharded dim over the data axes (ZeRO-3; weights
    are all-gathered per-layer by GSPMD, activations stay batch-sharded via
    ``transformer.constrain_batch``).
  * **Sequence parallel** (``rules.sequence_parallel``): the residual
    stream's *sequence* dim is sharded over the model axis between TP
    regions (Megatron-SP).  Applied by the step builders through
    ``transformer.set_batch_axes``; it changes activation placement only,
    never parameter placement.

Every rule is guarded by a divisibility check (``fit_axes``): a dim that
does not divide the mesh axis is simply left unsharded (e.g. smollm's 15
heads on a 16-way model axis) — the layout degrades, it never errors.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterable, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axes = Union[None, str, Tuple[str, ...]]

# Mesh axes considered data-parallel, in the order batch dims shard over
# them.  Mesh construction (repro.launch.mesh) only ever uses these names
# plus the model axis.
DATA_AXIS_ORDER: Tuple[str, ...] = ("pod", "data")

# Mixers whose state is recurrent (O(1) decode state): sequence parallelism
# interacts badly with their chunked scan (the per-chunk carry would cross
# shard boundaries every step), so the recommended rules disable SP.
_RECURRENT_MIXERS = frozenset({"mamba", "mlstm", "slstm"})
_ATTENTION_MIXERS = frozenset({"attn", "swa", "mla"})


# ---------------------------------------------------------------------------
# Rules
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Declarative knobs the autotuner searches over.

    ``ShardSpace`` (repro.core.shard_space) emits exactly these fields; the
    step builders translate them into concrete ``NamedSharding`` trees.
    """

    fsdp_weights: bool = False          # ZeRO-3: shard params over data axes
    sequence_parallel: bool = False     # Megatron-SP residual stream
    tp_axis: str = "model"              # mesh axis used for tensor parallel
    fsdp_min_size: int = 2 ** 16        # leave small params replicated

    @classmethod
    def recommended(cls, cfg) -> "ShardingRules":
        """Default production rules for an ``ArchConfig``.

        Sequence parallelism is ON only for pure-attention stacks: recurrent
        mixers scan over sequence chunks (the carry would cross shard
        boundaries) and MoE FFNs already pay an all-to-all on the token dim,
        so SP's gather/scatter pair costs more than the all-reduce it
        replaces (measured in the §Perf hillclimb).  FSDP is ON once the
        parameter body is large enough that replicated weights dominate HBM.
        """
        mixers = {m for m, _ in cfg.pattern}
        ffns = {f for _, f in cfg.pattern}
        pure_attention = mixers <= _ATTENTION_MIXERS
        has_moe = "moe" in ffns or cfg.n_experts > 0
        recurrent = bool(mixers & _RECURRENT_MIXERS)
        sp = pure_attention and not has_moe and not recurrent
        # ~ >1 GiB of bf16 block params: replication stops being free
        big = cfg.n_layers * cfg.d_model * max(
            cfg.d_ff, cfg.d_model) * max(cfg.n_experts, 1) >= 2 ** 29
        return cls(fsdp_weights=big, sequence_parallel=sp)

    def describe(self) -> str:
        return (f"tp={self.tp_axis} fsdp={'on' if self.fsdp_weights else 'off'}"
                f" sp={'on' if self.sequence_parallel else 'off'}")


# ---------------------------------------------------------------------------
# Axis arithmetic
# ---------------------------------------------------------------------------

def axis_size(mesh: Mesh, axes: Axes) -> int:
    """Product of the named mesh axes (missing axes count as 1)."""
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= int(mesh.shape.get(a, 1))
    return n


def data_axes(mesh: Mesh, tp_axis: str = "model") -> Tuple[str, ...]:
    """Mesh axes used for batch/data parallelism, in mesh order."""
    return tuple(a for a in mesh.axis_names
                 if a != tp_axis and a in DATA_AXIS_ORDER)


def fit_axes(n: int, axes: Axes, mesh: Mesh) -> Axes:
    """Largest dividing subset of ``axes``, kept in axis order — the
    universal divisibility fallback.  Axes absent from ``mesh`` are ignored,
    and an axis that does not divide the remaining factor of ``n`` is
    *skipped*, not a stopping point (n=6 over (pod=4, data=3) -> ("data",)).

    Returns axes in the same general shape they came in: a single name stays
    a name, a sequence comes back as a tuple; ``None`` when nothing fits.
    """
    if axes is None or n <= 0:
        return None
    single = isinstance(axes, str)
    candidates = (axes,) if single else tuple(axes)
    kept = []
    prod = 1
    for a in candidates:
        size = int(mesh.shape.get(a, 0))
        if size <= 0:
            continue                       # axis absent from this mesh
        if n % (prod * size) == 0:
            kept.append(a)
            prod *= size
    if not kept:
        return None
    if single:
        return kept[0]
    return tuple(kept)


def _path_names(path) -> Tuple[str, ...]:
    """Stringify a tree_util key path (DictKey / SequenceKey / attr)."""
    out = []
    for p in path:
        for attr in ("key", "name", "idx"):
            if hasattr(p, attr):
                out.append(str(getattr(p, attr)))
                break
        else:
            out.append(str(p))
    return tuple(out)


# ---------------------------------------------------------------------------
# Parameter shardings
# ---------------------------------------------------------------------------

# Column-parallel weights: shard the *output-feature* (last) dim.  The
# matching activations stay replicated on entry, sharded on exit.
_COLUMN = frozenset({
    "wq", "wk", "wv",            # attention qkv
    "wkv_b",                     # MLA per-head key/value expansion
    "w_gate", "w_up", "w_in",    # swiglu / gelu MLP up-projections
    "in_proj", "dt_proj",        # mamba expand + dt
    "wz", "wi", "wf",            # xLSTM input/gate projections
})
# Row-parallel weights: shard the *contraction* (first non-stack) dim; the
# product carries partial sums that GSPMD all-reduces once per block.
_ROW = frozenset({
    "wo",                        # attention output
    "w_down", "w_out",           # MLP down-projections
    "out_proj",                  # mamba output
    "wo_out",                    # sLSTM output
})
# Biases of column-parallel weights follow their output-feature sharding.
_COLUMN_BIAS = frozenset({"bq", "bk", "bv", "b_in"})
# Mamba per-channel (d_inner-indexed) vectors: keep them aligned with the
# in_proj output sharding so the selective scan runs fully sharded.
_CHANNEL_LAST = frozenset({"conv_w", "conv_b", "dt_bias", "D"})
_CHANNEL_FIRST = frozenset({"A_log"})
# MoE tensors carrying a leading expert dim (after the layer-stack dim).
_MOE_EXPERT = frozenset({"w_gate", "w_up", "w_down"})


def _param_spec(names: Tuple[str, ...], shape: Tuple[int, ...],
                mesh: Mesh, cfg, rules: ShardingRules) -> P:
    """PartitionSpec for one parameter leaf, identified by its tree path."""
    tp = rules.tp_axis
    ndim = len(shape)
    spec: list = [None] * ndim
    # Layer stacks carry a leading repeats dim (lax.scan axis) — never
    # sharded: every device owns every layer's slice of each weight.
    stacked = bool(names) and names[0] in ("layers", "enc_layers", "lead")
    off = 1 if stacked else 0
    name = names[-1] if names else ""

    if name == "embed" and ndim == 2:
        spec[0] = fit_axes(shape[0], tp, mesh)           # vocab rows
    elif name == "lm_head" and ndim == 2:
        spec[1] = fit_axes(shape[1], tp, mesh)           # vocab cols
    elif name in _MOE_EXPERT and ndim - off == 3:
        # MoE: (E, d_model, d_ff) / (E, d_ff, d_model) behind the stack dim.
        if fit_axes(shape[off], tp, mesh) is not None:
            spec[off] = tp                               # expert parallel
        elif name in ("w_gate", "w_up"):
            spec[ndim - 1] = fit_axes(shape[-1], tp, mesh)
        else:                                            # w_down
            spec[off + 1] = fit_axes(shape[off + 1], tp, mesh)
    elif name in _COLUMN and ndim - off == 2:
        spec[ndim - 1] = fit_axes(shape[-1], tp, mesh)
    elif name in _ROW and ndim - off == 2:
        spec[off] = fit_axes(shape[off], tp, mesh)
    elif name in _COLUMN_BIAS and ndim - off == 1:
        spec[ndim - 1] = fit_axes(shape[-1], tp, mesh)
    elif name in _CHANNEL_LAST and ndim - off >= 1:
        spec[ndim - 1] = fit_axes(shape[-1], tp, mesh)
    elif name in _CHANNEL_FIRST and ndim - off == 2:
        spec[off] = fit_axes(shape[off], tp, mesh)
    # everything else (norms, routers and their bias, MLA's latent
    # projection, recurrent r-mats): replicated

    if rules.fsdp_weights and int(np.prod(shape)) >= rules.fsdp_min_size:
        dp = data_axes(mesh, tp)
        for d in range(off, ndim):
            if spec[d] is None:
                ax = fit_axes(shape[d], dp, mesh)
                if ax:
                    spec[d] = ax
                    break
    return P(*spec)


def param_shardings(abstract_params: Any, mesh: Mesh, cfg,
                    rules: Optional[ShardingRules] = None) -> Any:
    """NamedSharding tree matching an (abstract) parameter tree.

    ``abstract_params`` is the output of ``transformer.abstract_params``
    (or a real parameter tree — only shapes are read).  Also the right
    sharding for gradients and Adam moments, which mirror the params.
    """
    rules = rules or ShardingRules()

    def one(path, leaf):
        spec = _param_spec(_path_names(path), tuple(leaf.shape), mesh, cfg,
                           rules)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, abstract_params)


# ---------------------------------------------------------------------------
# Batch / input shardings
# ---------------------------------------------------------------------------

def batch_sharding(mesh: Mesh, batch: int, seq: int,
                   tp_axis: str = "model") -> NamedSharding:
    """Sharding for a single (batch, seq) int token array."""
    del seq  # decode tokens are seq-len 1; seq stays unsharded here
    return NamedSharding(
        mesh, P(fit_axes(batch, data_axes(mesh, tp_axis), mesh)))


def batch_specs(batch_tree: Any, mesh: Mesh,
                tp_axis: str = "model") -> Any:
    """NamedSharding tree for a host batch: dim 0 over the data axes.

    Works on any pytree of arrays/ShapeDtypeStructs whose leaves all carry
    a leading global-batch dim (tokens, labels, patches, frames...).  Leaves
    whose batch does not divide the data axes stay replicated.
    """
    dp = data_axes(mesh, tp_axis)

    def one(leaf):
        spec = [None] * len(leaf.shape)
        if leaf.shape:
            spec[0] = fit_axes(leaf.shape[0], dp, mesh)
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(one, batch_tree)


def cache_shardings(abstract_cache: Any, mesh: Mesh, cfg,
                    rules: Optional[ShardingRules] = None) -> Any:
    """NamedSharding tree for a decode cache (``transformer.init_cache``).

    Layout: the per-sequence batch dim shards over the data axes; attention
    KV caches additionally shard the kv-head dim over the model axis (the
    serve-step attention then reduces over a sharded cache — flash-decoding
    semantics via GSPMD).  The cache *sequence* dim is never sharded: SWA
    ring-buffer writes are dynamic-slice updates at arbitrary offsets.
    """
    rules = rules or ShardingRules()
    dp = data_axes(mesh, rules.tp_axis)

    def one(path, leaf):
        names = _path_names(path)
        shape = tuple(leaf.shape)
        spec: list = [None] * len(shape)
        if names and names[0] == "pos":
            spec[0] = fit_axes(shape[0], dp, mesh)
        elif len(shape) >= 2:
            # layer entries are stacked (repeats, batch, ...)
            spec[1] = fit_axes(shape[1], dp, mesh)
            if len(shape) == 5 and names[-1] in ("k", "v", "xk", "xv"):
                spec[3] = fit_axes(shape[3], rules.tp_axis, mesh)
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(one, abstract_cache)


# ---------------------------------------------------------------------------
# Introspection / validation helpers
# ---------------------------------------------------------------------------

def validate_shardings(abstract: Any, shardings: Any) -> None:
    """Assert every spec'd dim divides evenly on its mesh axes.

    ``param_shardings``/``cache_shardings`` guarantee this by construction;
    this guards hand-built or deserialized sharding trees before they reach
    ``jax.jit`` (whose own error points at an HLO op, not a parameter).
    """
    flat_a = jax.tree_util.tree_flatten_with_path(abstract)[0]
    flat_s = jax.tree.leaves(
        shardings, is_leaf=lambda x: isinstance(x, NamedSharding))
    if len(flat_a) != len(flat_s):
        raise ValueError(
            f"tree mismatch: {len(flat_a)} leaves vs {len(flat_s)} shardings")
    for (path, leaf), sh in zip(flat_a, flat_s):
        if not isinstance(sh, NamedSharding):
            raise TypeError(f"{_path_names(path)}: {type(sh).__name__} "
                            "is not a NamedSharding")
        for d, axes in enumerate(sh.spec):
            if axes is None:
                continue
            size = axis_size(sh.mesh, axes)
            if leaf.shape[d] % size:
                raise ValueError(
                    f"{'/'.join(_path_names(path))}: dim {d} of shape "
                    f"{tuple(leaf.shape)} not divisible by {axes}={size}")


def describe_shardings(abstract: Any, shardings: Any,
                       max_rows: int = 0) -> str:
    """Human-readable placement table (dry-run debugging aid)."""
    flat_a = jax.tree_util.tree_flatten_with_path(abstract)[0]
    flat_s = jax.tree.leaves(
        shardings, is_leaf=lambda x: isinstance(x, NamedSharding))
    rows = []
    for (path, leaf), sh in zip(flat_a, flat_s):
        key = "/".join(_path_names(path))
        spec = tuple(sh.spec) + (None,) * (len(leaf.shape) - len(sh.spec))
        rows.append(f"{key:<48} {str(tuple(leaf.shape)):<28} {spec}")
    if max_rows and len(rows) > max_rows:
        rows = rows[:max_rows] + [f"... ({len(flat_a) - max_rows} more)"]
    return "\n".join(rows)


def param_bytes_per_device(abstract: Any, shardings: Any) -> int:
    """Per-device resident parameter bytes under a sharding tree — the
    number the roofline HBM-residency model cross-checks."""
    flat_a = jax.tree.leaves(abstract)
    flat_s = jax.tree.leaves(
        shardings, is_leaf=lambda x: isinstance(x, NamedSharding))
    total = 0
    for leaf, sh in zip(flat_a, flat_s):
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        shards = 1
        for axes in sh.spec:
            shards *= axis_size(sh.mesh, axes)
        total += (n // max(shards, 1)) * jax.dtypes.canonicalize_dtype(
            leaf.dtype).itemsize
    return total
