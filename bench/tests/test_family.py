"""The family a configuration file names through its ``reference`` key:
what the harness takes from it is what it took before the family was
looked up by name (golden values read on the commit before the lookup),
and a file that names no family module, or a program configuration of a
family its module does not describe, is refused."""
import hashlib
import os
import re

import jax
import numpy as np
import pytest

from bench import common, program
from bench.run import ROOT

SEED = 2 ** 31 + 4242
CONFIGS = {n: common.load_json(ROOT, "bench", "configs", n + ".json")
           for n in ("qwen2-1.5b", "smollm-360m")}

# sha256 (first 16 hex digits) of each seeded weight leaf at the program's
# reduced sizes, in the served type, from the seed above
WEIGHTS = {
    "qwen2-1.5b": {
        "['embed']": "188422bce34dd672", "['final_ln']": "baac558d2df57ea1",
        "['layers'][0]['ffn']['ln']": "bb89ae80557d2445",
        "['layers'][0]['ffn']['w_down']": "00e9647fe48e1d8b",
        "['layers'][0]['ffn']['w_gate']": "e8bfef1fdf1e8425",
        "['layers'][0]['ffn']['w_up']": "fb6184d98d29568e",
        "['layers'][0]['mix']['bk']": "76a93ff0f1c19901",
        "['layers'][0]['mix']['bq']": "e5ad0f9a8f27df55",
        "['layers'][0]['mix']['bv']": "f144f01f0a981451",
        "['layers'][0]['mix']['ln']": "7b7ebcad1d9d347a",
        "['layers'][0]['mix']['wk']": "6db7f92cd6c4a591",
        "['layers'][0]['mix']['wo']": "a1d3199fd6e0d501",
        "['layers'][0]['mix']['wq']": "d56bb8de29c0b083",
        "['layers'][0]['mix']['wv']": "53a55920b99a7f64",
        "['lm_head']": "9ce3e5a1c97bda8e"},
    "smollm-360m": {
        "['embed']": "8af6c78fe2999d39", "['final_ln']": "6fc524f6a27d69a1",
        "['layers'][0]['ffn']['ln']": "c4130497c05218f9",
        "['layers'][0]['ffn']['w_down']": "775347b3d68ab90f",
        "['layers'][0]['ffn']['w_gate']": "f449de754c2cdbb1",
        "['layers'][0]['ffn']['w_up']": "550cc5e6c1aa93ec",
        "['layers'][0]['mix']['ln']": "130205bc672b332c",
        "['layers'][0]['mix']['wk']": "3445e9beb5919007",
        "['layers'][0]['mix']['wo']": "b01f138e7f8e4fbd",
        "['layers'][0]['mix']['wq']": "772fe6c6eb2c6eff",
        "['layers'][0]['mix']['wv']": "7f13007d36993642",
        "['lm_head']": "2e1a23a2b3c173d0"},
}
# the readers' counts at full size: decode bytes and operations at
# (tokens, summed context), prefill operations at a prompt length, and
# training operations per token of a 2048-token row
COUNTS = {
    "qwen2-1.5b": {
        "decode_bytes/1/100": 3090935296, "decode_flops/1/100": 3104342016,
        "decode_bytes/7/9000": 3349953024,
        "decode_flops/7/9000": 23158259712,
        "decode_bytes/32/131072": 6865988608,
        "decode_flops/32/131072": 121337020416,
        "prefill_flops/128": 337297145856,
        "prefill_flops/2048": 5727981797376,
        "prefill_flops/3968": 11752845213696,
        "train_flops_per_token/2048": 9790156800},
    "smollm-360m": {
        "decode_bytes/1/100": 1455758848, "decode_flops/1/100": 735805440,
        "decode_bytes/7/9000": 2186541056,
        "decode_flops/7/9000": 6170542080,
        "decode_bytes/32/131072": 12193738496,
        "decode_flops/32/131072": 39258685440,
        "prefill_flops/128": 81639505920,
        "prefill_flops/2048": 1546408427520,
        "prefill_flops/3968": 3464162181120,
        "train_flops_per_token/2048": 2548224000},
}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_seeded_weights_are_unchanged(name):
    c = CONFIGS[name]
    params = program.make_params(c, program.arch_config(c, reduced=True),
                                 SEED)
    got = {jax.tree_util.keystr(k): hashlib.sha256(
        np.asarray(v).tobytes()).hexdigest()[:16]
        for k, v in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert got == WEIGHTS[name]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_counts_are_unchanged(name):
    c = CONFIGS[name]
    fam = program.family(c)
    got = {}
    for t, ctx in ((1, 100), (7, 9000), (32, 131072)):
        got[f"decode_bytes/{t}/{ctx}"] = fam.decode_bytes(c, t, ctx)
        got[f"decode_flops/{t}/{ctx}"] = fam.decode_flops(c, t, ctx)
    for s in (128, 2048, 3968):
        got[f"prefill_flops/{s}"] = fam.prefill_flops(c, s)
    got["train_flops_per_token/2048"] = fam.train_flops_per_token(c, 2048)
    assert got == COUNTS[name]


@pytest.mark.parametrize("path", ["bench/reference/moe.py",
                                  "bench/reference/dense",
                                  "bench/drivers/serve.py",
                                  "bench/reference/__init__.py"])
def test_a_reference_that_is_no_family_module_is_refused(path):
    c = dict(CONFIGS["qwen2-1.5b"], reference=path)
    with pytest.raises(ValueError, match=re.escape(path)):
        program.arch_config(c)


@pytest.mark.parametrize("arch", ["moonshot-v1-16b-a3b", "mixtral-8x22b",
                                  "jamba-1.5-large-398b"])
def test_a_family_the_module_does_not_describe_is_refused(arch):
    c = dict(CONFIGS["qwen2-1.5b"], arch=arch)
    for reduced in (False, True):
        with pytest.raises(ValueError, match="plain dense stack"):
            program.arch_config(c, reduced=reduced)


def test_only_the_lookup_names_a_family():
    """The drivers, readers and tools reach the dense family through the
    configuration file alone; only its own module and tests name it."""
    own = {os.path.join("bench", "reference", "dense.py"),
           os.path.join("bench", "tests", "test_reference.py"),
           os.path.join("bench", "tests", "test_flops.py"),
           os.path.relpath(__file__, ROOT)}
    named = set()
    for d, _, files in os.walk(os.path.join(ROOT, "bench")):
        for f in (f for f in files if f.endswith(".py")):
            with open(os.path.join(d, f)) as fh:
                if re.search(r"reference\.dense|import dense", fh.read()):
                    named.add(os.path.relpath(os.path.join(d, f), ROOT))
    assert named <= own
