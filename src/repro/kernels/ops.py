"""Jit'd public wrappers around the Pallas kernels.

Backend dispatch: on TPU the kernels compile through Mosaic; on the CPU
(tests) they execute under ``interpret=True`` so tests validate the exact
kernel bodies; any other backend raises (``interpret_mode``).
``use_pallas=False`` falls back to the jnp oracle — that path is what the 512-device dry-run lowers (Pallas does not partition
across GSPMD meshes; the kernels are the per-core fast path).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode, ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.gemm import GemmConfig, gemm as _gemm, gemm_config_from_knobs


@functools.partial(jax.jit, static_argnames=("config", "use_pallas"))
def matmul(a: jnp.ndarray, b: jnp.ndarray,
           config: GemmConfig = GemmConfig(),
           use_pallas: bool = True) -> jnp.ndarray:
    if not use_pallas:
        return ref.matmul_ref(a, b)
    return _gemm(a, b, config, interpret=interpret_mode())


def im2col(x: jnp.ndarray, kh: int, kw: int, stride: int, pad: int
           ) -> Tuple[jnp.ndarray, Tuple[int, int]]:
    """x: (B, H, W, CI) -> patches (B*OH*OW, KH*KW*CI), plus (OH, OW).

    Feature ordering matches ``w.reshape(KH*KW*CI, CO)`` for HWIO weights.
    """
    b, h, w_, ci = x.shape
    patches = jax.lax.conv_general_dilated_patches(
        x, (kh, kw), (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    oh, ow = patches.shape[1], patches.shape[2]
    # conv_general_dilated_patches emits features as (CI, KH, KW) —
    # reorder to (KH, KW, CI) to match HWIO weight flattening.
    patches = patches.reshape(b, oh, ow, ci, kh, kw)
    patches = patches.transpose(0, 1, 2, 4, 5, 3)
    return patches.reshape(b * oh * ow, kh * kw * ci), (oh, ow)


@functools.partial(jax.jit,
                   static_argnames=("stride", "pad", "config", "use_pallas"))
def conv2d(x: jnp.ndarray, w: jnp.ndarray, stride: int = 1, pad: int = 0,
           config: GemmConfig = GemmConfig(),
           use_pallas: bool = True) -> jnp.ndarray:
    """Conv as im2col + the tunable GEMM core. x: NHWC, w: HWIO."""
    if not use_pallas:
        return ref.conv2d_ref(x, w, stride, pad)
    b = x.shape[0]
    kh, kw, ci, co = w.shape
    patches, (oh, ow) = im2col(x, kh, kw, stride, pad)
    out = _gemm(patches, w.reshape(kh * kw * ci, co), config,
                interpret=interpret_mode())
    return out.reshape(b, oh, ow, co)


def conv2d_from_knobs(x, w, stride, pad, *, tile_b, tile_h, tile_w,
                      tile_ci, tile_co, h_threading, oc_threading,
                      use_pallas: bool = True):
    """Execute a conv with an ARCO configuration (knob values)."""
    kh, kw = w.shape[0], w.shape[1]
    cfg = gemm_config_from_knobs(
        tile_m=tile_b * tile_h * tile_w,
        tile_n=tile_co,
        tile_k=tile_ci * kh * kw,
        h_threading=h_threading, oc_threading=oc_threading)
    return conv2d(x, w, stride, pad, cfg, use_pallas)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "use_pallas"))
def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
              causal: bool = True, window: Optional[int] = None,
              block_q: int = 128, block_k: int = 128,
              use_pallas: bool = True) -> jnp.ndarray:
    if not use_pallas:
        return ref.attention_ref(q, k, v, causal=causal, window=window)
    return _flash(q, k, v, causal=causal, window=window,
                  block_q=block_q, block_k=block_k,
                  interpret=interpret_mode())
