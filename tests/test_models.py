"""Per-architecture smoke tests (reduced configs) + decode consistency."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_NAMES, all_configs, get_config
from repro.configs.shapes import SHAPES, cell_supported, input_specs
from repro.models import layers as L
from repro.models import transformer as T


def _batch_for(cfg, rng, b=2, s=48):
    text = s - cfg.vision_prefix if cfg.vision_prefix else s
    batch = {"tokens": jax.random.randint(rng, (b, text), 0, cfg.vocab),
             "labels": jax.random.randint(rng, (b, text), 0, cfg.vocab)}
    if cfg.vision_prefix:
        batch["patches"] = jax.random.normal(
            rng, (b, cfg.vision_prefix, cfg.d_model), cfg.dtype)
    if cfg.enc_dec:
        batch["frames"] = jax.random.normal(
            rng, (b, cfg.enc_seq, cfg.d_model), cfg.dtype)
    return batch


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_reduced_train_step(arch):
    """One forward/loss on CPU: correct shapes, finite, loss ~ log V."""
    cfg = get_config(arch, reduced=True)
    rng = jax.random.PRNGKey(0)
    params = T.init_params(rng, cfg)
    batch = _batch_for(cfg, rng)
    loss, metrics = T.loss_fn(params, batch, cfg)
    assert np.isfinite(float(loss))
    assert 0.5 * np.log(cfg.vocab) < float(loss) < 2.5 * np.log(cfg.vocab)
    grads = jax.grad(lambda p: T.loss_fn(p, batch, cfg)[0])(params)
    gn = sum(float(jnp.sum(jnp.square(g.astype(jnp.float32))))
             for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_reduced_prefill_decode(arch):
    cfg = get_config(arch, reduced=True)
    rng = jax.random.PRNGKey(0)
    params = T.init_params(rng, cfg)
    batch = {k: v for k, v in _batch_for(cfg, rng).items() if k != "labels"}
    # a model whose experts count hands its counters back third
    logits, cache, *_ = T.prefill(params, batch, cfg, max_len=64)
    assert logits.shape == (2, cfg.vocab)
    tok = jnp.argmax(logits, -1)[:, None]
    logits2, cache2, *_ = T.decode_step(params, cache, tok, cfg)
    assert logits2.shape == (2, cfg.vocab)
    assert bool(jnp.isfinite(logits2).all())
    assert bool((cache2["pos"] == cache["pos"] + 1).all())


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "mixtral-8x22b",
                                  "xlstm-1.3b", "jamba-1.5-large-398b",
                                  "whisper-base"])
def test_decode_matches_prefill_fp32(arch):
    """Teacher-forced decode must reproduce prefill logits (fp32)."""
    cfg = get_config(arch, reduced=True).with_(
        remat=False, dtype=jnp.float32, param_dtype=jnp.float32)
    rng = jax.random.PRNGKey(0)
    params = T.init_params(rng, cfg)
    s = 13
    batch = _batch_for(cfg, rng, b=1, s=s)
    batch.pop("labels")
    full_tokens = batch["tokens"]
    pre = dict(batch, tokens=full_tokens[:, :s - 1 - (cfg.vision_prefix and 0)])
    pre["tokens"] = full_tokens[:, :-1]
    _, cache = T.prefill(params, pre, cfg, max_len=32)
    ld, _ = T.decode_step(params, cache, full_tokens[:, -1:], cfg)
    lfull, _ = T.prefill(params, batch, cfg, max_len=32)
    np.testing.assert_allclose(np.asarray(ld), np.asarray(lfull),
                               rtol=1e-3, atol=1e-4)


def test_swa_ring_cache_long_decode():
    """Mixtral ring cache: decoding past the window stays finite and
    matches a non-ring cache within the window."""
    cfg = get_config("mixtral-8x22b", reduced=True).with_(
        remat=False, dtype=jnp.float32, param_dtype=jnp.float32)
    assert cfg.swa_window == 16
    rng = jax.random.PRNGKey(0)
    params = T.init_params(rng, cfg)
    toks = jax.random.randint(rng, (1, 40), 0, cfg.vocab)
    # ring cache: max_len == window -> ring buffer
    _, ring_cache = T.prefill(params, {"tokens": toks[:, :8]}, cfg,
                              max_len=cfg.swa_window)
    # big cache: no ring
    _, big_cache = T.prefill(params, {"tokens": toks[:, :8]}, cfg,
                             max_len=64)
    for i in range(8, 30):
        lr, ring_cache = T.decode_step(params, ring_cache, toks[:, i:i + 1],
                                       cfg)
        lb, big_cache = T.decode_step(params, big_cache, toks[:, i:i + 1],
                                      cfg)
        np.testing.assert_allclose(np.asarray(lr), np.asarray(lb),
                                   rtol=2e-3, atol=1e-3)


def _decode_by_layer(params, cache, tokens, cfg):
    """One decode step, layer by layer in Python, each block given its
    layer's cache sliced out as a stack of one."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    rows = []
    for r in range(cfg.repeats):
        row = []
        for pidx, (mixer, ffn) in enumerate(cfg.pattern):
            p = jax.tree.map(lambda a: a[r], params["layers"][pidx])
            entry = jax.tree.map(lambda a: a[r:r + 1], cache["layers"][pidx])
            x, new, _ = T._decode_block(x, p, cfg, mixer, ffn, entry, 0,
                                        cache["pos"])
            row.append(new)
        rows.append(row)
    layers = tuple(jax.tree.map(lambda *a: jnp.concatenate(a),
                                *[row[pidx] for row in rows])
                   for pidx in range(cfg.period))
    logits = T.logits_last(params, L.rmsnorm(x, params["final_ln"]), cfg)
    return logits, layers


@pytest.mark.parametrize("arch,n_layers,max_len,pos", [
    ("qwen2-1.5b", 4, 16, [3, 10, 0, 15]),          # dense attention
    ("mixtral-8x22b", 4, 16, [5, 17, 30, 16]),      # SWA ring, wrapped
    ("jamba-1.5-large-398b", 16, 16, [3, 10, 0, 7]),  # mamba + attention
])
def test_decode_step_writes_only_each_slots_position(arch, n_layers,
                                                     max_len, pos):
    """Per-slot positions that differ: the step writes K/V at (layer, slot,
    pos[slot] mod S) and leaves every other cache entry bit-identical, and
    its logits and cache match the step computed layer by layer."""
    cfg = get_config(arch, reduced=True).with_(
        n_layers=n_layers, remat=False, dtype=jnp.float32,
        param_dtype=jnp.float32)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    b = len(pos)
    cache = T.init_cache(cfg, b, max_len)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    cache = {"pos": jnp.asarray(pos, jnp.int32),
             "layers": jax.tree.map(
                 lambda a: jax.random.normal(next(keys), a.shape, a.dtype),
                 cache["layers"])}
    tokens = jax.random.randint(jax.random.PRNGKey(2), (b, 1), 0, cfg.vocab)
    logits, new = T.decode_step(params, cache, tokens, cfg)
    ref_logits, ref_layers = _decode_by_layer(params, cache, tokens, cfg)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=1e-5, atol=1e-5)
    for got, want in zip(jax.tree.leaves(new["layers"]),
                         jax.tree.leaves(ref_layers)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    n_kv = 0
    for mixer, old, now in zip((m for m, _ in cfg.pattern), cache["layers"],
                               new["layers"]):
        if mixer not in ("attn", "swa"):
            continue
        for name in ("k", "v"):
            was, got = np.asarray(old[name]), np.asarray(now[name])
            written = np.zeros(was.shape[:3], bool)
            written[:, np.arange(b), np.asarray(pos) % was.shape[2]] = True
            np.testing.assert_array_equal(got[~written], was[~written])
            assert (got[written] != was[written]).all()
            n_kv += 1
    assert n_kv == 2
    np.testing.assert_array_equal(np.asarray(new["pos"]),
                                  np.asarray(pos) + 1)


def test_param_counts_full_configs():
    """Full (non-reduced) configs hit their published scale (abstract)."""
    expected = {  # total params, tolerance band
        "qwen2-1.5b": (1.2e9, 2.2e9),
        "smollm-360m": (0.3e9, 0.5e9),
        "qwen1.5-4b": (3e9, 5e9),
        "minitron-4b": (3.4e9, 5.8e9),
        "mixtral-8x22b": (1.2e11, 1.6e11),
        # published: 15.96B in all, 2.91B active per token
        "moonlight-16b-a3b": (1.5e10, 1.7e10),
        "jamba-1.5-large-398b": (3.2e11, 4.6e11),
        "xlstm-1.3b": (0.9e9, 1.8e9),
        "internvl2-26b": (1.5e10, 2.6e10),  # backbone only (no ViT)
        "whisper-base": (0.5e8, 1.2e8),
    }
    for arch, (lo, hi) in expected.items():
        cfg = get_config(arch)
        ab = T.abstract_params(jax.random.PRNGKey(0), cfg)
        n = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(ab))
        assert lo <= n <= hi, f"{arch}: {n:.3e} not in [{lo:.1e},{hi:.1e}]"


def test_long_context_rule():
    sub_q = {a for a in ARCH_NAMES
             if cell_supported(get_config(a), SHAPES["long_500k"])[0]}
    assert sub_q == {"mixtral-8x22b", "xlstm-1.3b", "jamba-1.5-large-398b"}


@pytest.mark.parametrize("arch", ARCH_NAMES)
@pytest.mark.parametrize("shape", list(SHAPES))
def test_input_specs_no_allocation(arch, shape):
    cfg = get_config(arch)
    cell = SHAPES[shape]
    ok, _ = cell_supported(cfg, cell)
    if not ok:
        pytest.skip("cell skipped by long-context rule")
    spec = input_specs(cfg, cell)
    for leaf in jax.tree.leaves(spec):
        assert isinstance(leaf, jax.ShapeDtypeStruct)
    if cell.kind == "train":
        assert spec["tokens"].shape[0] == cell.global_batch
    if cell.kind == "decode":
        assert spec["tokens"].shape == (cell.global_batch, 1)


def test_moe_dense_vs_dropping_close():
    """With generous capacity, dropping == dense routing math."""
    from repro.models import moe as MOE
    rng = jax.random.PRNGKey(0)
    p = MOE.init_moe(rng, 32, 64, 4, jnp.float32)
    x = jax.random.normal(rng, (2, 16, 32), jnp.float32)
    yd, _ = MOE.moe_dense(x, p, 2)
    yc, _ = MOE.moe_dropping(x, p, 2, capacity_factor=4.0, group_size=32)
    np.testing.assert_allclose(np.asarray(yd), np.asarray(yc), rtol=2e-3,
                               atol=2e-3)
