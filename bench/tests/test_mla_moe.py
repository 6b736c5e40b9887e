"""The latent-attention expert family (``bench/reference/mla_moe.py``) as
the harness takes it: looked up through the configuration file, checked
against the program's configuration, its counts at full size, and the
reader that holds a scope's device time to the bytes the family counts."""
from types import SimpleNamespace as NS

import jax
import numpy as np
import pytest

from bench import common, program
from bench.readers import scope_roofline
from bench.reference import mla_moe
from bench.run import ROOT
from bench.tests.test_trace_reduce import DECODE, _hlo, _program_planes
from bench import trace_reduce

SEED = 2 ** 31 + 4242
CONF = common.load_json(ROOT, "bench", "configs", "moonlight-16b-a3b.json")
# the counts at full size: decode bytes and operations at (tokens, summed
# context), the attention and expert scopes' bytes, prefill operations at
# a prompt length, with and without the program's pair counter
COUNTS = {
    "decode_bytes/1/100": 6068761984, "decode_flops/1/100": 5185855488,
    "attention_bytes/1/100": 746426880,
    "decode_bytes/7/9000": 6349730944, "decode_flops/7/9000": 38595772416,
    "attention_bytes/7/9000": 1023252480,
    "decode_bytes/32/131072": 10163922432,
    "decode_flops/32/131072": 201301426176,
    "attention_bytes/32/131072": 4820179968,
    "experts_bytes": 3598712832, "experts_bytes/hit100": 1730150400,
    "decode_flops/7/9000/pairs500": 28353282048,
    "prefill_flops/128": 577304920064, "prefill_flops/1024": 4740577492992,
    "prefill_flops/3072": 15090121179136,
    "prefill_flops/1024/pairs20000": 2322796118016,
    "latent_bytes": 31104}


def test_the_file_names_this_family_and_the_program_matches_it():
    assert program.family(CONF) is mla_moe
    cfg = program.arch_config(CONF)
    assert (cfg.n_experts, cfg.n_held_experts, cfg.first_expert) == (64, 8, 0)
    assert (cfg.rms_eps, cfg.rope_theta, cfg.moe_top_k) == (1e-5, 5e4, 6)
    spec = mla_moe.spec_of(CONF, cfg)
    assert spec == mla_moe.MlaMoe.from_config(CONF)
    assert (spec.held, spec.n_experts, spec.n_layers, spec.n_dense) == (
        8, 64, 27, 1)


@pytest.mark.parametrize("key,value", [
    ("num_experts_per_tok", 8), ("kv_lora_rank", 256),
    ("n_shared_experts", 1), ("first_k_dense_replace", 2),
    ("routed_scaling_factor", 1.0), ("scoring_func", "softmax"),
    ("q_lora_rank", 1536)])
def test_a_file_the_program_does_not_run_is_refused(key, value):
    c = dict(CONF, **{key: value})
    with pytest.raises(ValueError, match="moonlight"):
        program.arch_config(c)


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "mixtral-8x22b"])
def test_another_family_is_refused(arch):
    for reduced in (False, True):
        with pytest.raises(ValueError, match="latent attention"):
            program.arch_config(dict(CONF, arch=arch), reduced=reduced)


@pytest.mark.parametrize("name", ["train_reference", "leaf_norms", "AdamW",
                                  "train_flops_per_token"])
def test_the_training_names_refuse(name):
    with pytest.raises(ValueError, match="serve-only"):
        getattr(mla_moe, name)()


def test_seeded_weights_fit_the_program_at_the_reduced_size():
    cfg = program.arch_config(CONF, reduced=True)
    params = program.make_params(CONF, cfg, SEED)
    back = mla_moe.to_program(mla_moe.to_reference(params))
    assert jax.tree.structure(back) == jax.tree.structure(params)
    assert params["layers"][0]["ffn"]["router"].dtype == np.float32
    assert params["layers"][0]["ffn"]["w_up"].shape[1] == cfg.held_experts


def test_counts_at_full_size():
    c, f = CONF, mla_moe
    got = {}
    for t, x in ((1, 100), (7, 9000), (32, 131072)):
        got[f"decode_bytes/{t}/{x}"] = f.decode_bytes(c, t, x)
        got[f"decode_flops/{t}/{x}"] = f.decode_flops(c, t, x)
        got[f"attention_bytes/{t}/{x}"] = f.decode_scope_bytes(
            c, "attention", t, x)
    got["experts_bytes"] = f.decode_scope_bytes(c, "experts", 32, 0)
    got["experts_bytes/hit100"] = f.decode_scope_bytes(c, "experts", 32, 0,
                                                       experts_hit=100)
    got["decode_flops/7/9000/pairs500"] = f.decode_flops(c, 7, 9000,
                                                         pairs=500)
    for s in (128, 1024, 3072):
        got[f"prefill_flops/{s}"] = f.prefill_flops(c, s)
    got["prefill_flops/1024/pairs20000"] = f.prefill_flops(c, 1024,
                                                           pairs=20000)
    got["latent_bytes"] = f.latent_bytes(c)
    assert got == COUNTS
    assert all(isinstance(v, int) for v in got.values())
    # the held experts' weights: 26 layers x 8 x 3 x 2048 x 1408 in bf16
    assert got["experts_bytes"] == 26 * 8 * 3 * 2048 * 1408 * 2
    # a latent position of every layer: 27 x (512 + 64) x 2 bytes
    assert got["latent_bytes"] == 27 * 576 * 2


def test_scope_roofline_on_a_recorded_trace():
    """The decode program's ``attention`` scope took 250 ns over its two
    executions in the built trace; the steps of the traced part ask for the
    bytes the family counts, over that time at the peak bandwidth."""
    trace = trace_reduce.reduce_planes(_program_planes(), mla_moe.SCOPES,
                                       {DECODE: _hlo()})
    window = 20.0
    t0 = common.SubTrace.START * window
    steps = [{"t0": t0 + 0.1, "t1": t0 + 0.12, "decoded": 4,
              "context": 4000, "admitted": 0},
             {"t0": t0 + 0.2, "t1": t0 + 0.22, "decoded": 6,
              "context": 7000, "admitted": 1},
             # before the traced part, and one that decoded nothing
             {"t0": t0 - 1.0, "t1": t0 - 0.9, "decoded": 9,
              "context": 1, "admitted": 0},
             {"t0": t0 + 0.3, "t1": t0 + 0.4, "decoded": 0,
              "context": 0, "admitted": 1}]
    run = NS(trace=trace, steps=steps, window_s=window)
    peaks = {"hbm_bytes_per_s": 819e9}
    got = scope_roofline.read(run, CONF, peaks, "jit_serve_decode",
                              "attention")
    need = (mla_moe.decode_scope_bytes(CONF, "attention", 4, 4000)
            + mla_moe.decode_scope_bytes(CONF, "attention", 6, 7000)) / 2
    assert trace["programs"]["jit_serve_decode"]["scope_ms"][
        "attention"] == pytest.approx(125e-6)
    assert got == pytest.approx(100 * need / 819e9 / 125e-9)
    assert scope_roofline.read(run, CONF, peaks, "jit_serve_decode",
                               "experts") is None      # did not run
    assert scope_roofline.read(NS(trace=trace, steps=steps[2:],
                                  window_s=window), CONF, peaks,
                               "jit_serve_decode", "attention") is None
    dense = common.load_json(ROOT, "bench", "configs", "qwen2-1.5b.json")
    assert scope_roofline.read(run, dense, peaks, "jit_serve_decode",
                               "attention") is None


def test_the_scopes_nest_as_the_program_names_them():
    for op_name, scope in [
            ("jit(serve_decode)/while/body/moe/experts/ragged_dot", "experts"),
            ("jit(serve_decode)/while/body/moe/argsort", "moe"),
            ("jit(serve_decode)/while/body/attention/kv_update/scatter",
             "kv_update"),
            ("jit(serve_prefill)/while/body/mlp/dot_general", "mlp")]:
        assert trace_reduce.op_scope(op_name, mla_moe.SCOPES) == scope
