"""Tunable tiled GEMM Pallas kernel — the TPU analog of the VTA GEMM core.

The ARCO hardware agent's knobs instantiate this kernel's geometry:

    tile_m (BATCH x spatial tiles)  -> BlockSpec M tile
    tile_k (BLOCK_IN  analog)       -> BlockSpec K tile
    tile_n (BLOCK_OUT analog)       -> BlockSpec N tile

and the scheduling agent's knobs choose grid *dimension semantics*
("threading": parallel vs arbitrary sequencing of the M/N grid) and the
K-split: whether the contraction is blocked over the grid's innermost
dimension (accumulating in a VMEM scratch accumulator) or kept whole.

Target is TPU (Mosaic); on this CPU-only container the kernel runs under
``interpret=True`` and is validated against ``ref.matmul_ref``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



@dataclasses.dataclass(frozen=True)
class GemmConfig:
    """Kernel geometry — the knobs ARCO tunes."""
    block_m: int = 128
    block_n: int = 128
    block_k: int = 128
    # scheduling-agent knobs
    parallel_m: bool = True    # h_threading analog: M grid dim parallel
    parallel_n: bool = True    # oc_threading analog: N grid dim parallel
    # derived VMEM working set (bytes) for feasibility checks
    def vmem_bytes(self, in_dtype=jnp.bfloat16) -> int:
        b = jnp.dtype(in_dtype).itemsize
        return (self.block_m * self.block_k * b
                + self.block_k * self.block_n * b
                + self.block_m * self.block_n * 4)


def _gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pad_dim(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    rem = (-x.shape[axis]) % mult
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads)


def gemm(a: jnp.ndarray, b: jnp.ndarray,
         config: GemmConfig = GemmConfig(),
         out_dtype: Optional[jnp.dtype] = None,
         interpret: bool = False) -> jnp.ndarray:
    """C = A @ B with explicit BlockSpec tiling. a: (M, K), b: (K, N)."""
    if a.ndim != 2 or b.ndim != 2 or a.shape[1] != b.shape[0]:
        raise ValueError(f"bad gemm shapes {a.shape} {b.shape}")
    m, k = a.shape
    _, n = b.shape
    out_dtype = out_dtype or a.dtype
    bm = min(config.block_m, m)
    bn = min(config.block_n, n)
    bk = min(config.block_k, k)

    a = _pad_dim(_pad_dim(a, 0, bm), 1, bk)
    b = _pad_dim(_pad_dim(b, 0, bk), 1, bn)
    mp, kp = a.shape
    _, np_ = b.shape
    grid = (mp // bm, np_ // bn, kp // bk)

    sem_m = "parallel" if config.parallel_m else "arbitrary"
    sem_n = "parallel" if config.parallel_n else "arbitrary"

    out = pl.pallas_call(
        functools.partial(_gemm_kernel, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(sem_m, sem_n, "arbitrary")),
        interpret=interpret,
    )(a, b)
    return out[:m, :n]


def gemm_config_from_knobs(tile_m: int, tile_n: int, tile_k: int,
                           h_threading: int, oc_threading: int) -> GemmConfig:
    """Map ARCO knob values onto a kernel geometry.

    Tile values are rounded up to hardware granules (8 sublanes / 128 lanes);
    threading>1 marks the corresponding grid dimension parallel.
    """
    rup = lambda v, g: max(g, int(-(-int(v) // g) * g))
    return GemmConfig(
        block_m=rup(tile_m, 8),
        block_n=rup(tile_n, 128),
        block_k=rup(tile_k, 128),
        parallel_m=h_threading > 1,
        parallel_n=oc_threading > 1,
    )
