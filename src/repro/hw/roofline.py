"""Roofline terms per (arch x shape x mesh) cell.

Three terms (seconds per step, per the brief):

  compute    = HLO_FLOPs_per_device / peak_FLOP/s
               (trip-count-weighted dot FLOPs parsed from partitioned HLO —
                XLA's cost_analysis visits loop bodies once, see
                hw/hlo_analysis.py)
  memory     = HBM_bytes_per_device / HBM_bw
               (analytic traffic model: CPU-backend buffer numbers include
                f32-promotion artifacts that don't exist on TPU, so HBM
                traffic is modelled from first principles: weight streaming
                per pass, activation saves, KV-cache reads)
  collective = wire_bytes_per_device / ICI_link_bw
               (trip-count-weighted collective bytes, ring multipliers)

Plus MODEL_FLOPS = 6*N_active*D (train) / 2*N_active*tokens (inference) and
the usefulness ratio MODEL_FLOPS / HLO_FLOPS.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import numpy as np

from repro.hw.tpu_spec import DEFAULT, TpuSpec
from repro.models.transformer import ArchConfig, abstract_params


def _param_counts(cfg: ArchConfig) -> Dict[str, float]:
    """(total, active) parameter counts; active scales MoE experts to top_k."""
    ab = abstract_params(jax.random.PRNGKey(0), cfg)
    total = sum(float(np.prod(l.shape)) for l in jax.tree.leaves(ab))
    active = total
    if cfg.n_experts and cfg.moe_top_k:
        moe = 0.0
        for p, (mixer, ffn) in enumerate(cfg.pattern):
            if ffn != "moe":
                continue
            stack = ab["layers"][p]["ffn"]
            for name in ("w_gate", "w_up", "w_down"):
                moe += float(np.prod(stack[name].shape))
        active = total - moe * (1.0 - cfg.moe_top_k / cfg.n_experts)
    return {"total": total, "active": active}


def _attn_layers(cfg: ArchConfig) -> int:
    return sum(1 for m, _ in cfg.layer_kinds if m in ("attn", "swa", "mla"))


def model_flops(cfg: ArchConfig, kind: str, seq: int, batch: int,
                counts: Optional[Dict[str, float]] = None) -> float:
    """Useful model FLOPs for the whole step (all devices)."""
    c = counts or _param_counts(cfg)
    na = c["active"]
    la = _attn_layers(cfg)
    hd = cfg.head_dim * cfg.n_heads
    if kind == "train":
        tokens = batch * seq
        attn = 2.0 * 2.0 * batch * seq * seq * hd * la / 2.0  # causal half
        if cfg.swa_window:
            attn = 2.0 * 2.0 * batch * seq * min(seq, cfg.swa_window) \
                * hd * la
        return 6.0 * na * tokens + 3.0 * attn
    if kind == "prefill":
        tokens = batch * seq
        attn = 2.0 * 2.0 * batch * seq * seq * hd * la / 2.0
        if cfg.swa_window:
            attn = 2.0 * 2.0 * batch * seq * min(seq, cfg.swa_window) \
                * hd * la
        return 2.0 * na * tokens + attn
    # decode: one token per sequence; attends over the whole cache
    ctx = min(seq, cfg.swa_window) if cfg.swa_window else seq
    attn = 2.0 * 2.0 * batch * ctx * hd * la
    return 2.0 * na * batch + attn


def kv_cache_bytes(cfg: ArchConfig, seq: int, batch: int) -> float:
    """Global decode-state bytes (KV caches + recurrent states)."""
    dt = 2.0  # bf16
    total = 0.0
    for mixer, _ in cfg.layer_kinds:     # one term per layer
        if mixer == "mla":
            total += batch * seq * (cfg.kv_lora_rank
                                    + cfg.qk_rope_head_dim) * dt
        elif mixer in ("attn", "swa"):
            s = min(seq, cfg.swa_window) if (mixer == "swa"
                                             and cfg.swa_window) else seq
            total += 2 * batch * s * cfg.n_kv_heads * cfg.head_dim * dt
        elif mixer == "mamba":
            di = 2 * cfg.d_model
            total += batch * di * (cfg.d_state + 3) * 4.0
        elif mixer in ("mlstm",):
            dh = cfg.head_dim
            total += batch * cfg.n_heads * (dh * dh + dh + 1) * 4.0
        elif mixer == "slstm":
            total += batch * 4 * cfg.d_model * 4.0
    return total


def memory_traffic(cfg: ArchConfig, kind: str, seq: int, batch: int,
                   mesh: Dict[str, int],
                   counts: Optional[Dict[str, float]] = None) -> float:
    """Per-device HBM bytes per step (analytic TPU model)."""
    c = counts or _param_counts(cfg)
    model_par = mesh.get("model", 1)
    n_dev = int(np.prod(list(mesh.values())))
    dp = n_dev // model_par
    p_use = c["total"] * 2.0 / model_par     # bf16 weights streamed per pass
    b_loc = max(batch // dp, 1)
    act = b_loc * seq * cfg.d_model * 2.0    # one residual-stream tensor
    if kind == "train":
        # fwd read + bwd read + remat re-read of weights; grads write+read;
        # opt m/v read+write (bf16) + param write
        weights = 3.0 * p_use + 4.0 * (c["total"] * 2.0 / n_dev) * 2.0
        # activation saves: one per layer boundary, written + read
        acts = 2.0 * act * cfg.n_layers
        return weights + acts
    if kind == "prefill":
        return p_use + act * 2.0
    # decode: weights once + full cache read, sharded across all devices
    return p_use + kv_cache_bytes(cfg, seq, batch) / n_dev + \
        2.0 * b_loc * cfg.d_model * 2.0 * cfg.n_layers


def hbm_residency(cfg: ArchConfig, kind: str, seq: int, batch: int,
                  mesh: Dict[str, int], *, fsdp: bool = True,
                  moment_dtype: str = "bfloat16", remat: bool = True,
                  grad_accum: int = 1, sequence_parallel: bool = False,
                  counts: Optional[Dict[str, float]] = None) -> float:
    """Modelled steady-state HBM bytes per device (TPU target).

    The Eq.4 'memory(theta)' analog for pod-level configurations: params +
    grads + optimizer moments (sharding-dependent) + activation saves
    (remat-policy-dependent) + a 2 GiB transient allowance.
    """
    c = counts or _param_counts(cfg)
    n_dev = int(np.prod(list(mesh.values())))
    tp = mesh.get("model", 1)
    dp = max(n_dev // tp, 1)
    if kind != "train":
        weights = c["total"] * 2.0 / (tp if not fsdp else n_dev)
        cache = kv_cache_bytes(cfg, seq, batch) / n_dev \
            if kind == "decode" else 0.0
        b_loc = max(batch // dp, 1)
        act = b_loc * seq * cfg.d_model * 2.0 if kind == "prefill" else 0.0
        return weights + cache + 2.0 * act + 2 * 2.0 ** 30
    shards = n_dev if fsdp else tp
    params = c["total"] * 2.0 / shards
    grads = params
    mom = c["total"] * (8.0 if moment_dtype == "float32" else 4.0) / shards
    b_loc = max(batch // dp, 1) / max(grad_accum, 1)
    act = b_loc * seq * cfg.d_model * 2.0
    if sequence_parallel:
        act /= tp   # SP shards the saved residual stream over the TP axis
    acts = (cfg.repeats * act) if remat else (cfg.n_layers * 2.5 * act)
    return params + grads + mom + acts + 2 * 2.0 ** 30


@dataclasses.dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float
    hlo_flops: float
    usefulness: float
    step_s: float

    def as_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


def analyze_cell(cfg: ArchConfig, kind: str, seq: int, batch: int,
                 mesh: Dict[str, int], artifact: Dict[str, Any],
                 spec: TpuSpec = DEFAULT) -> Roofline:
    """Combine the dry-run artifact with the analytic model."""
    counts = _param_counts(cfg)
    n_dev = int(np.prod(list(mesh.values())))
    flops_dev = float(artifact["weighted"]["dot_flops_per_device"])
    compute_s = flops_dev / spec.peak_bf16_flops
    mem_bytes = memory_traffic(cfg, kind, seq, batch, mesh, counts)
    memory_s = mem_bytes / spec.hbm_bw
    wire = float(artifact["weighted"]["wire_bytes_per_device"])
    collective_s = wire / spec.ici_bw_per_link
    mf = model_flops(cfg, kind, seq, batch, counts)
    hlo_total = flops_dev * n_dev
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    return Roofline(
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        dominant=dominant, model_flops=mf, hlo_flops=hlo_total,
        usefulness=mf / hlo_total if hlo_total else 0.0,
        step_s=max(terms.values()))


def roofline_fraction(r: Roofline, spec: TpuSpec = DEFAULT,
                      n_dev: int = 256) -> float:
    """Achieved fraction of the hardware roofline: useful FLOPs at the
    modelled step time vs peak."""
    if r.step_s <= 0:
        return 0.0
    return (r.model_flops / n_dev / r.step_s) / spec.peak_bf16_flops
