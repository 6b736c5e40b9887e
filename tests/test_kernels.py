"""Per-kernel allclose vs the pure-jnp oracles (interpret mode on CPU),
with shape/dtype sweeps and hypothesis property tests."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # dependency-light env: seeded spot-checks instead
    from _hypothesis_compat import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.gemm import GemmConfig, gemm_config_from_knobs

jax.config.update("jax_enable_x64", False)


# ----------------------------------------------------------------- gemm

GEMM_SHAPES = [(8, 8, 8), (100, 70, 90), (128, 128, 128), (1, 256, 33),
               (257, 129, 65)]
GEMM_CONFIGS = [GemmConfig(32, 32, 32), GemmConfig(128, 128, 128),
                GemmConfig(16, 64, 128, parallel_m=False),
                GemmConfig(8, 128, 256, parallel_n=False)]


@pytest.mark.parametrize("m,k,n", GEMM_SHAPES)
@pytest.mark.parametrize("cfg", GEMM_CONFIGS[:2])
def test_gemm_shapes(m, k, n, cfg):
    a = jax.random.normal(jax.random.PRNGKey(0), (m, k), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32)
    out = ops.matmul(a, b, cfg)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.matmul_ref(a, b)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("cfg", GEMM_CONFIGS)
def test_gemm_configs(cfg):
    a = jax.random.normal(jax.random.PRNGKey(2), (96, 80), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(3), (80, 112), jnp.float32)
    out = ops.matmul(a, b, cfg)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.matmul_ref(a, b)),
                               rtol=1e-5, atol=1e-5)


def test_gemm_bf16():
    a = jax.random.normal(jax.random.PRNGKey(4), (64, 64), jnp.bfloat16)
    b = jax.random.normal(jax.random.PRNGKey(5), (64, 64), jnp.bfloat16)
    out = ops.matmul(a, b, GemmConfig(32, 32, 32))
    expect = ref.matmul_ref(a, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=3e-2, atol=3e-2)


@settings(max_examples=15, deadline=None)
@given(m=st.integers(1, 80), k=st.integers(1, 80), n=st.integers(1, 80),
       bm=st.sampled_from([8, 16, 32]), bn=st.sampled_from([16, 32, 64]),
       bk=st.sampled_from([16, 32, 64]))
def test_gemm_property(m, k, n, bm, bn, bk):
    """Any tile geometry yields the same product (padding correctness)."""
    a = jax.random.normal(jax.random.PRNGKey(m * 83 + k), (m, k),
                          jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(n), (k, n), jnp.float32)
    out = ops.matmul(a, b, GemmConfig(bm, bn, bk))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.matmul_ref(a, b)),
                               rtol=2e-5, atol=2e-5)


def test_gemm_knob_mapping():
    cfg = gemm_config_from_knobs(tile_m=7, tile_n=100, tile_k=60,
                                 h_threading=2, oc_threading=1)
    assert cfg.block_m % 8 == 0 and cfg.block_n % 128 == 0
    assert cfg.block_k % 128 == 0
    assert cfg.parallel_m and not cfg.parallel_n


# ----------------------------------------------------------------- conv2d

@pytest.mark.parametrize("stride,pad", [(1, 1), (2, 0), (2, 1), (1, 0)])
@pytest.mark.parametrize("kh", [1, 3])
def test_conv2d(stride, pad, kh):
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 13, 13, 5), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(7), (kh, kh, 5, 7), jnp.float32)
    out = ops.conv2d(x, w, stride, pad, GemmConfig(32, 32, 64))
    expect = ref.conv2d_ref(x, w, stride, pad)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-4)


def test_conv2d_from_knobs():
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 14, 14, 16), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(9), (3, 3, 16, 32), jnp.float32)
    out = ops.conv2d_from_knobs(x, w, 1, 1, tile_b=1, tile_h=4, tile_w=4,
                                tile_ci=16, tile_co=32, h_threading=2,
                                oc_threading=2)
    expect = ref.conv2d_ref(x, w, 1, 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-4)


# ------------------------------------------------------- flash attention

@pytest.mark.parametrize("causal,window", [(True, None), (False, None),
                                           (True, 32)])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (6, 1)])
def test_flash_attention(causal, window, hq, hkv):
    q = jax.random.normal(jax.random.PRNGKey(10), (2, 100, hq, 16),
                          jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(11), (2, 100, hkv, 16),
                          jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(12), (2, 100, hkv, 16),
                          jnp.float32)
    out = ops.attention(q, k, v, causal=causal, window=window,
                        block_q=32, block_k=32)
    expect = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-3, atol=2e-3)


@settings(max_examples=10, deadline=None)
@given(s=st.integers(3, 70), bq=st.sampled_from([16, 32]),
       bk=st.sampled_from([16, 64]), causal=st.booleans())
def test_flash_attention_property(s, bq, bk, causal):
    """Block sizes never change the result (online-softmax correctness)."""
    q = jax.random.normal(jax.random.PRNGKey(s), (1, s, 2, 8), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(s + 1), (1, s, 2, 8),
                          jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(s + 2), (1, s, 2, 8),
                          jnp.float32)
    out = ops.attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    expect = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-3, atol=2e-3)


def test_chunked_attention_matches_ref():
    """The differentiable training-path attention == oracle."""
    from repro.models.layers import chunked_attention
    q = jax.random.normal(jax.random.PRNGKey(20), (2, 50, 4, 16),
                          jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(21), (2, 50, 2, 16),
                          jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(22), (2, 50, 2, 16),
                          jnp.float32)
    for chunk in (7, 16, 50, 128):
        out = chunked_attention(q, k, v, causal=True, chunk=chunk)
        expect = ref.attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-3, atol=2e-3)


def test_chunked_attention_grads_finite():
    from repro.models.layers import chunked_attention

    def f(q, k, v):
        return chunked_attention(q, k, v, chunk=16).sum()

    q = jax.random.normal(jax.random.PRNGKey(23), (1, 33, 2, 8), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(24), (1, 33, 2, 8), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(25), (1, 33, 2, 8), jnp.float32)
    grads = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    for g in grads:
        assert bool(jnp.isfinite(g).all())


# ----------------------------------------------------------------- rmsnorm

@pytest.mark.parametrize("shape", [(4, 64), (2, 100, 96), (1, 7, 33),
                                   (129, 256)])
@pytest.mark.parametrize("block_rows", [8, 32, 128])
def test_rmsnorm_kernel(shape, block_rows):
    from repro.kernels.rmsnorm import rmsnorm
    from repro.models.layers import rmsnorm as ref_rmsnorm
    x = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), shape[-1:], jnp.float32)
    out = rmsnorm(x, w, block_rows=block_rows)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref_rmsnorm(x, w)),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------- backend dispatch

@pytest.mark.parametrize("backend,interpret", [("tpu", False), ("cpu", True),
                                               ("gpu", None)])
def test_interpret_mode_only_on_cpu(monkeypatch, backend, interpret):
    """Mosaic on a TPU, the interpreter on the CPU, an error elsewhere:
    no backend silently falls back to the slow interpreter."""
    from repro.kernels import interpret_mode
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="gpu"):
            interpret_mode()
    else:
        assert interpret_mode() is interpret
