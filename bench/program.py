"""What the harness takes from the system under test, in one place: the
model configuration as the program names it, and the layout of the
program's parameter tree, which the harness fills with the seeded weights
of ``bench/reference``.
"""
from __future__ import annotations

import os
import sys
from functools import partial

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

from repro.configs import get_config  # noqa: E402
from repro.models import transformer as T  # noqa: E402

from bench.reference import dense  # noqa: E402

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
# program name -> reference name, per sub-tree of one layer
MIX = {"ln": "attn_norm", "wq": "wq", "wk": "wk", "wv": "wv", "wo": "wo",
       "bq": "bq", "bk": "bk", "bv": "bv"}
FFN = {"ln": "mlp_norm", "w_gate": "w_gate", "w_up": "w_up",
       "w_down": "w_down"}
TOP = {"embed": "embed", "final_ln": "final_norm", "lm_head": "lm_head"}


def arch_config(c: dict, reduced: bool = False) -> T.ArchConfig:
    """The program's configuration for the configuration file ``c``, with
    the RoPE base and the precision the file states; refuses one whose
    sizes disagree, and a tied head on the train path (the program trains
    its output head as a parameter of its own)."""
    cfg = get_config(c["arch"], reduced=reduced).with_(
        rope_theta=float(c["rope_theta"]))
    if not reduced:
        want = {"vocab": c["vocab_size"], "d_model": c["hidden_size"],
                "n_layers": c["num_hidden_layers"],
                "n_heads": c["num_attention_heads"],
                "n_kv_heads": c["num_key_value_heads"],
                "d_ff": c["intermediate_size"], "qkv_bias": c["qkv_bias"]}
        got = {k: getattr(cfg, k) for k in want}
        want["rms_norm_eps"], got["rms_norm_eps"] = c["rms_norm_eps"], 1e-6
        if got != want:
            raise ValueError(f"{c['arch']}: the program runs {got}, the "
                             f"configuration file states {want}")
    if cfg.pattern != (("attn", "mlp"),) or cfg.family != "dense":
        raise ValueError(f"{c['arch']} is not a plain dense stack")
    if c["path"] == "train" and c["tie_word_embeddings"]:
        raise ValueError(f"{c['arch']}: the program cannot train a tied "
                         f"output head")
    return cfg.with_(param_dtype=DTYPES[c["param_dtype"]],
                     dtype=DTYPES[c["compute_dtype"]])


def spec_of(cfg: T.ArchConfig, tied: bool = False) -> dense.Dense:
    """The reference's description of the model the program runs (the
    program's RMSNorm epsilon is fixed at 1e-6). With ``tied`` the served
    head is the embedding's transpose, which the program holds as a copy."""
    return dense.Dense(vocab=cfg.vocab, d_model=cfg.d_model,
                       n_layers=cfg.n_layers, n_heads=cfg.n_heads,
                       n_kv_heads=cfg.n_kv_heads, d_ff=cfg.d_ff,
                       qkv_bias=cfg.qkv_bias, rope_theta=cfg.rope_theta,
                       rms_eps=1e-6, tied=tied)


def to_program(ref: dict) -> dict:
    """Reference layout (globals + stacked layers) -> the program's tree."""
    lay = ref["layers"]
    mix = {p: lay[r] for p, r in MIX.items() if r in lay}
    ffn = {p: lay[r] for p, r in FFN.items()}
    return {**{p: ref[r] for p, r in TOP.items()},
            "layers": ({"mix": mix, "ffn": ffn},)}


def to_reference(prog: dict) -> dict:
    """The program's tree -> reference layout."""
    blk = prog["layers"][0]
    layers = {r: blk["mix"][p] for p, r in MIX.items() if p in blk["mix"]}
    layers.update({r: blk["ffn"][p] for p, r in FFN.items()})
    return {**{r: prog[p] for p, r in TOP.items()}, "layers": layers}


def make_params(cfg: T.ArchConfig, seed: int, tied: bool = False):
    """The seeded weights in the program's layout and served type, made on
    the device in one jitted call."""
    spec = spec_of(cfg, tied)
    build = jax.jit(partial(dense.init_params, spec=spec,
                            dtype=cfg.param_dtype))
    params = to_program(build(dense.seed_key(seed)))
    want = T.abstract_params(jax.random.PRNGKey(0), cfg)
    shapes = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if shapes != jax.tree.map(lambda a: (a.shape, a.dtype), want):
        raise ValueError("the seeded weights do not fit the program's "
                         "parameter tree")
    return params
