"""Pallas TPU kernel: ALEA sample-attribution reduction.

TPU adaptation of the tool's aggregation hot spot (billions of samples on
a fleet): instead of a scatter-add histogram (GPU-style atomics — no TPU
analogue), each sample block is turned into a one-hot matrix and the three
statistics become MXU matmuls:

    counts += 1ᵀ · onehot      psum += powᵀ · onehot      psumsq += (pow²)ᵀ · onehot

Layout (Mosaic-friendly, everything 2-D): ids and powers stream as
``[1, block_n]`` lane-major blocks. Each step builds the statistic rows
``[8, block_n]`` = (1, pow, pow², 0-padding) and the transposed tile-local
one-hot ``[block_r, block_n]`` (sublane iota == ids), and issues ONE
``[8, block_n] · [block_r, block_n]ᵀ`` MXU matmul at full f32 precision
into an ``[8, block_r]`` accumulator block.

Grid: (region tiles, sample blocks), sample axis innermost. Each region
tile's accumulator block is the output block (same block across the
whole inner sweep → VMEM-resident); sample blocks stream HBM→VMEM. The
region axis is tiled so num_regions is unbounded: R > block_r (e.g. the
10⁴–10⁵ multi-worker combination space) never overflows VMEM — the
one-hot tile (block_r × block_n × 4 B, 8 MB at the defaults, plus its
iota/compare temporaries) is the VMEM budget regardless of R; the v5e
compiler accepts it within the default scoped-VMEM limit
(``tests/test_chip_compile.py``). Samples are re-streamed once per region
tile; the region-tile loop is the classic reduction-tiling tradeoff
(R/block_r × sample traffic for O(block_r) on-chip state).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

DEFAULT_BLOCK_N = 1024
DEFAULT_BLOCK_R = 2048
_ROWS = 8       # statistic rows (1, pow, pow², zero padding): one sublane tile


def _kernel(ids_ref, pow_ref, out_ref, *, block_r: int):
    j = pl.program_id(0)   # region tile (outer)
    i = pl.program_id(1)   # sample block (inner; accumulator stays resident)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ids = ids_ref[...]                                  # [1, bn] int32
    pw = pow_ref[...]                                   # [1, bn] f32
    bn = ids.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, bn), 0)
    stats = jnp.where(row == 0, 1.0,
                      jnp.where(row == 1, pw,
                                jnp.where(row == 2, pw * pw, 0.0)))
    # Tile-local one-hot, transposed: region on sublanes, sample on lanes.
    # Ids outside this tile (and -1 padding) match no row → zero columns.
    local = ids - j * block_r
    onehot_t = (jax.lax.broadcasted_iota(jnp.int32, (block_r, bn), 0)
                == local).astype(jnp.float32)           # [block_r, bn]
    out_ref[...] += jax.lax.dot_general(
        stats, onehot_t, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)             # [8, block_r]


def sample_attr_pallas(region_ids: jnp.ndarray, powers: jnp.ndarray,
                       num_regions: int, *, block_n: int = DEFAULT_BLOCK_N,
                       block_r: int | None = None,
                       interpret: bool = False):
    """region_ids: [n] int32 (pad with -1); powers: [n] f32.

    ``block_r`` tiles the region axis (default: min(num_regions, 2048));
    any ``num_regions`` is supported — the region space is padded up to a
    multiple of ``block_r`` and the outputs sliced back. Returns float32
    (counts, Σpow, Σpow²), each [num_regions].
    """
    if block_r is None:
        block_r = min(num_regions, DEFAULT_BLOCK_R)
    n = region_ids.shape[0]
    n_pad = (block_n - n % block_n) % block_n
    if n_pad:
        region_ids = jnp.concatenate(
            [region_ids, jnp.full((n_pad,), -1, region_ids.dtype)])
        powers = jnp.concatenate([powers, jnp.zeros((n_pad,), powers.dtype)])
    r_pad = (block_r - num_regions % block_r) % block_r
    num_r_padded = num_regions + r_pad
    grid = (num_r_padded // block_r, region_ids.shape[0] // block_n)

    # Block indices stay int32 when traced under enable_x64 (the fused
    # pipeline's carry): a bare 0 would widen to an int64 index, which
    # Mosaic cannot lower.
    zero = np.int32(0)
    sample_spec = pl.BlockSpec((1, block_n), lambda j, i: (zero, i))
    out = pl.pallas_call(
        functools.partial(_kernel, block_r=block_r),
        grid=grid,
        in_specs=[sample_spec, sample_spec],
        out_specs=pl.BlockSpec((_ROWS, block_r), lambda j, i: (zero, j)),
        out_shape=jax.ShapeDtypeStruct((_ROWS, num_r_padded), jnp.float32),
        interpret=interpret,
        name="sample_attr",
    )(region_ids.reshape(1, -1), powers.astype(jnp.float32).reshape(1, -1))
    return (out[0, :num_regions], out[1, :num_regions],
            out[2, :num_regions])
