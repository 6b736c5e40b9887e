"""The plain reference against the program's model (``repro.models.
transformer``) at reduced sizes, on seeded weights, everything float32.

Tolerances: both sides compute in float32 on the CPU but sum in other
orders (the program's attention is an online softmax over key chunks, the
reference a plain softmax; the program scans its layers), so they differ by
float32 rounding grown over two layers: a few 1e-6 of the largest logit.
1e-4 leaves room for that and is still orders of magnitude below what a
wrong mask, position, bias or norm gives (O(1) of the logits)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import common, program
from bench.run import ROOT
from bench.reference import dense
from repro.configs import get_config
from repro.models import transformer as T
from repro.train.server import _insert_slot

REL = 1e-4
SEED = 2 ** 31 + 77


def _setup(arch, tied=False):
    cfg = get_config(arch, reduced=True).with_(param_dtype=jnp.float32,
                                               dtype=jnp.float32)
    spec = program.spec_of(cfg, tied)
    params = program.make_params(cfg, SEED, tied)
    return cfg, spec, params


def _ref_logits(spec, tokens):
    """Reference logits at every position of one row, float32."""
    p = dense.init_params(dense.seed_key(SEED), spec, jnp.float32)
    x = p["embed"][jnp.asarray(tokens)][None]
    for i in range(spec.n_layers):
        x = dense.block(x, jax.tree.map(lambda a: a[i], p["layers"]), spec,
                        "f32", attn_chunk=len(tokens))
    h = dense.rmsnorm(x[0], p["final_norm"], spec.rms_eps)
    return np.asarray(dense.mm(h, p["lm_head"], "f32"))


def _close(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() / np.abs(b).max()


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "smollm-360m"])
def test_prefill_matches_reference(arch):
    cfg, spec, params = _setup(arch)
    toks = np.random.default_rng(0).integers(0, cfg.vocab, 24).astype(
        np.int32)
    logits, _ = T.prefill(params, {"tokens": jnp.asarray(toks[None])}, cfg,
                          32)
    assert _close(logits[0], _ref_logits(spec, toks)[-1]) < REL


def test_tied_head_is_the_embedding_and_matches_reference():
    """A tied configuration: the program's own head holds the embedding's
    transpose, and its prefill agrees with the tied reference."""
    cfg, spec, params = _setup("qwen2-1.5b", tied=True)
    np.testing.assert_array_equal(params["lm_head"], params["embed"].T)
    toks = np.random.default_rng(3).integers(0, cfg.vocab, 24).astype(
        np.int32)
    logits, _ = T.prefill(params, {"tokens": jnp.asarray(toks[None])}, cfg,
                          32)
    assert _close(logits[0], _ref_logits(spec, toks)[-1]) < REL


def test_configuration_file_sets_the_rope_base_and_refuses_a_tied_train():
    qwen = common.load_json(ROOT, "bench", "configs", "qwen2-1.5b.json")
    assert program.arch_config(qwen).rope_theta == 1e6
    assert program.arch_config(qwen, reduced=True).rope_theta == 1e6
    smol = common.load_json(ROOT, "bench", "configs", "smollm-360m.json")
    with pytest.raises(ValueError):
        program.arch_config(dict(smol, tie_word_embeddings=True))


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "smollm-360m"])
def test_batched_decode_matches_reference(arch):
    """Two prompts of different lengths prefilled, inserted into slots of
    one batched cache, then decoded together for three steps."""
    cfg, spec, params = _setup(arch)
    rng = np.random.default_rng(1)
    rows = [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in (9, 17)]
    cache = T.init_cache(cfg, 2, 32)
    for slot, r in enumerate(rows):
        _, rc = T.prefill(params, {"tokens": jnp.asarray(r[None, :-3])},
                          cfg, 32)
        cache = _insert_slot(cache, rc, slot)
    for k in (3, 2, 1):
        last = jnp.asarray([[r[-k]] for r in rows])
        logits, cache = T.decode_step(params, cache, last, cfg)
        for slot, r in enumerate(rows):
            want = _ref_logits(spec, r)[len(r) - k]
            assert _close(logits[slot], want) < REL, (slot, k)


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "smollm-360m"])
def test_loss_and_grad_match_reference(arch):
    cfg, spec, params = _setup(arch)
    rng = np.random.default_rng(2)
    tokens = rng.integers(0, cfg.vocab, (3, 16)).astype(np.int32)
    labels = np.concatenate([tokens[:, 1:], -np.ones((3, 1), np.int32)], 1)
    batch = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels)}
    (loss, _), grads = jax.value_and_grad(T.loss_fn, has_aux=True)(
        params, batch, cfg)
    ref_loss, ref_grads = dense.loss_and_grad(
        program.to_reference(params), jnp.asarray(tokens),
        jnp.asarray(labels), spec, "f32")
    assert abs(float(loss) - float(ref_loss)) / float(ref_loss) < REL
    got = dense.leaf_norms(program.to_reference(grads))
    want = dense.leaf_norms(ref_grads)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=REL, err_msg=k)


def test_served_logit_gaps_are_zero_for_reference_tokens():
    """Greedy tokens of the reference itself lie 0 below its best."""
    cfg, spec, _ = _setup("qwen2-1.5b")
    prompt = np.arange(5, 13, dtype=np.int32)
    served = []
    row = list(prompt)
    for _ in range(4):
        nxt = int(np.argmax(_ref_logits(spec, np.asarray(row,
                                                         np.int32))[-1]))
        served.append(nxt)
        row.append(nxt)
    gaps = dense.served_logit_gaps(spec, SEED,
                                   [(prompt, np.asarray(served, np.int32))],
                                   pad_to=16, dtype=jnp.float32)
    np.testing.assert_allclose(gaps[0], 0.0, atol=1e-5)
    other = dense.served_logit_gaps(
        spec, SEED, [(prompt, (np.asarray(served) + 1) % cfg.vocab)],
        pad_to=16, dtype=jnp.float32)
    assert other[0].min() > 0
