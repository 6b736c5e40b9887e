"""The Pallas kernels, and the decode step, compiled for a described TPU
v5e at model widths.

Nothing runs: each test compiles one kernel for a chip of a ``v5e:2x2``
topology described by the installed TPU compiler, and checks that the
program holds a Mosaic kernel (``tpu_custom_call``). This catches what
interpret mode cannot: blocks not aligned to the (8, 128) tiling and
kernels that need more VMEM than the chip gives them.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the pytest-xdist
workers import every test file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gemm import gemm, gemm_config_from_knobs
from repro.kernels.rmsnorm import rmsnorm
from repro.models import transformer as T


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these out of it."""
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _arg(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("tile", [128, 512])
def test_gemm_compiles_to_mosaic(one_chip, tile):
    """qwen2-1.5b's FFN up-projection over 4096 tokens: 4096x1536 @
    1536x8960, geometry from the ARCO knob mapping."""
    cfg = gemm_config_from_knobs(tile_m=tile, tile_n=tile, tile_k=tile,
                                 h_threading=2, oc_threading=2)
    a = _arg((4096, 1536), jnp.bfloat16, one_chip)
    b = _arg((1536, 8960), jnp.bfloat16, one_chip)
    text = _compiled_text(lambda a, b: gemm(a, b, cfg, interpret=False),
                          a, b)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("heads,kv_heads,head_dim,block", [
    (12, 2, 128, 128),     # qwen2-1.5b
    (12, 2, 128, 512),
    (15, 5, 64, 128),      # smollm-360m
])
def test_flash_attention_compiles_to_mosaic(one_chip, heads, kv_heads,
                                            head_dim, block):
    q = _arg((1, 2048, heads, head_dim), jnp.bfloat16, one_chip)
    kv = _arg((1, 2048, kv_heads, head_dim), jnp.bfloat16, one_chip)
    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        block_q=block, block_k=block,
                                        interpret=False), q, kv, kv)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("d_model", [1536, 960])
def test_rmsnorm_compiles_to_mosaic(one_chip, d_model):
    x = _arg((4096, d_model), jnp.bfloat16, one_chip)
    w = _arg((d_model,), jnp.bfloat16, one_chip)
    text = _compiled_text(lambda x, w: rmsnorm(x, w, interpret=False), x, w)
    assert "tpu_custom_call" in text


def test_decode_step_writes_the_donated_cache_in_place(one_chip):
    """qwen2-1.5b widths at 2 layers, 8 slots x 1024 positions: with the
    cache donated, the layer scan allocates no stacked leaf and copies
    none; its scratch memory is below one layer's K."""
    cfg = get_config("qwen2-1.5b").with_(n_layers=2)
    slots, max_len = 8, 1024

    def on_chip(tree):
        return jax.tree.map(lambda a: _arg(a.shape, a.dtype, one_chip), tree)

    params = on_chip(T.abstract_params(jax.random.PRNGKey(0), cfg))
    cache = on_chip(jax.eval_shape(lambda: T.init_cache(cfg, slots, max_len)))
    tokens = _arg((slots, 1), jnp.int32, one_chip)
    compiled = jax.jit(lambda p, c, t: T.decode_step(p, c, t, cfg),
                       donate_argnums=(1,)).lower(params, cache,
                                                  tokens).compile()
    leaf = cache["layers"][0]["k"]
    layer_k_bytes = leaf.size // leaf.shape[0] * leaf.dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < layer_k_bytes
    stacked = ",".join(map(str, leaf.shape))
    assert not re.search(rf"=\s*\w+\[{stacked}\]\S*\s+"
                         rf"(copy\(|custom-call\(.*AllocateBuffer)",
                         compiled.as_text())
