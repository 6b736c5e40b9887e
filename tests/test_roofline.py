"""Roofline model tests: analytic FLOPs/traffic formulas + cell analysis."""
import numpy as np
import pytest

from repro.configs import get_config
from repro.hw import roofline as RL
from repro.hw.tpu_spec import DEFAULT


def test_param_counts_active_vs_total_moe():
    cfg = get_config("mixtral-8x22b")
    c = RL._param_counts(cfg)
    # 8 experts top-2: active ~ total * ~(2/8) on the expert share
    assert c["active"] < 0.45 * c["total"]
    dense = get_config("qwen2-1.5b")
    cd = RL._param_counts(dense)
    assert cd["active"] == cd["total"]


def test_model_flops_train_matches_6nd():
    cfg = get_config("qwen2-1.5b")
    c = RL._param_counts(cfg)
    seq, batch = 4096, 256
    mf = RL.model_flops(cfg, "train", seq, batch, c)
    base = 6.0 * c["total"] * seq * batch
    assert base <= mf <= 1.5 * base  # attention adds a bounded extra


def test_decode_flops_linear_in_batch():
    cfg = get_config("minitron-4b")
    f1 = RL.model_flops(cfg, "decode", 32768, 1)
    f128 = RL.model_flops(cfg, "decode", 32768, 128)
    assert abs(f128 / f1 - 128) < 1


def test_swa_caps_attention_flops():
    cfg = get_config("mixtral-8x22b")
    c = RL._param_counts(cfg)
    f_32k = RL.model_flops(cfg, "prefill", 32768, 1, c)
    # without SWA the quadratic term would dominate; with window 4096 the
    # attention share stays < the projection share
    proj = 2.0 * c["active"] * 32768
    assert f_32k < 2.2 * proj


def test_kv_cache_bytes_swa_ring():
    cfg = get_config("mixtral-8x22b")
    full = RL.kv_cache_bytes(cfg.with_(swa_window=None), 524288, 1)
    ring = RL.kv_cache_bytes(cfg, 524288, 1)
    assert ring < full / 100  # window 4096 vs 524288


def test_memory_traffic_decode_dominated_by_weights_or_cache():
    cfg = get_config("moonlight-16b-a3b")
    mesh = {"data": 16, "model": 16}
    m = RL.memory_traffic(cfg, "decode", 32768, 128, mesh)
    assert m > 0
    # must be at least the TP-sharded weight stream
    c = RL._param_counts(cfg)
    assert m >= c["total"] * 2.0 / 16


def test_analyze_cell_and_fraction():
    cfg = get_config("qwen2-1.5b")
    art = {"weighted": {"dot_flops_per_device": 1e14,
                        "wire_bytes_per_device": 1e10,
                        "collective_bytes_by_op": {}}}
    r = RL.analyze_cell(cfg, "train", 4096, 256,
                        {"data": 16, "model": 16}, art)
    assert r.dominant in ("compute", "memory", "collective")
    assert r.step_s == max(r.compute_s, r.memory_s, r.collective_s)
    frac = RL.roofline_fraction(r, n_dev=256)
    assert 0 < frac <= 1.5
