"""jit'd public wrappers for the sample-attribution kernel.

``sample_attr(ids, powers, R)`` dispatches to the Pallas kernel on TPU and
to interpret mode elsewhere; ``as_aggregate_fn`` adapts it to the
estimator's pluggable aggregation interface.

Streaming path: ``chunked_aggregate_fn`` returns an AggregateFn whose
underlying ``pallas_call`` jit is cached by (block_n, block_r, num_regions)
via :func:`sample_attr_chunk` — short chunks are topped up in a
preallocated scratch buffer (two small copies, no per-chunk allocation) so
every chunk of a stream hits the same compiled executable (one trace per
configuration, not one per chunk length).

Fused device pipeline: :func:`make_carry_update` is the reduction seam of
:mod:`repro.core.device_pipeline` — a traceable function folding one
masked fixed-shape chunk *into* the pipeline's device-resident
(counts, Σpow, Σpow²) carry. On TPU it routes through the Pallas one-hot
matmul kernel (mask → ``-1`` ids, which match no one-hot column); on CPU
it lowers to the equivalent scatter-add (compiled XLA, not interpret
mode) with masked lanes dropped via an out-of-bounds index.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.sample_attr.sample_attr import (DEFAULT_BLOCK_N,
                                                   sample_attr_pallas)


@functools.partial(jax.jit, static_argnums=(2, 3))
def sample_attr(region_ids, powers, num_regions: int,
                interpret: bool | None = None):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return sample_attr_pallas(region_ids.astype(jnp.int32),
                              powers.astype(jnp.float32), num_regions,
                              interpret=interpret)


def as_aggregate_fn(interpret: bool | None = None):
    """Adapter matching estimator.AggregateFn (returns numpy)."""
    def agg(region_ids, powers, num_regions):
        c, s, sq = sample_attr(jnp.asarray(region_ids), jnp.asarray(powers),
                               int(num_regions), interpret)
        return (np.asarray(c).astype(np.int64), np.asarray(s, np.float64),
                np.asarray(sq, np.float64))
    return agg


@functools.lru_cache(maxsize=None)
def sample_attr_chunk(block_n: int, block_r: int | None, num_regions: int,
                      interpret: bool):
    """Compiled fixed-shape chunk reducer, cached by configuration.

    Returns a jitted ``fn(ids[capacity] i32, powers[capacity] f32) ->
    (counts, psum, psumsq)``; the pallas_call is built once per
    (block_n, block_r, num_regions, interpret) and the jit cache is keyed
    on the fixed chunk shape, so a streaming aggregator calling it per
    block never re-traces.
    """
    @jax.jit
    def run(region_ids, powers):
        return sample_attr_pallas(region_ids.astype(jnp.int32),
                                  powers.astype(jnp.float32), num_regions,
                                  block_n=block_n, block_r=block_r,
                                  interpret=interpret)
    return run


def chunked_aggregate_fn(chunk_capacity: int = 16 * DEFAULT_BLOCK_N, *,
                         block_n: int = DEFAULT_BLOCK_N,
                         block_r: int | None = None,
                         interpret: bool | None = None):
    """AggregateFn for ``StreamingAggregator``: fixed-capacity Pallas chunks.

    Short chunks (< ``chunk_capacity`` samples) are topped up in a
    preallocated scratch buffer with region_id = -1 (zero one-hot rows),
    so every update reuses one compiled kernel without allocating — two
    small copies into the scratch instead of four fresh arrays per chunk.
    Oversized chunks are folded in capacity-sized slices. The returned
    closure owns its scratch, so it is not safe to share one aggregate fn
    across threads (each ``StreamingAggregator`` should get its own).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    scratch_ids = np.full(chunk_capacity, -1, np.int32)
    scratch_pw = np.zeros(chunk_capacity, np.float32)

    def agg(region_ids, powers, num_regions):
        # Quantize the region axis to the next power of two (≥64) so a
        # growing region space (streaming combination interning) hits at
        # most O(log R) compiled kernels instead of one per distinct R.
        num_regions = int(num_regions)
        r_quant = max(64, 1 << (num_regions - 1).bit_length())
        fn = sample_attr_chunk(block_n, block_r, r_quant, bool(interpret))
        ids = np.asarray(region_ids, dtype=np.int32)
        pw = np.asarray(powers, dtype=np.float32)
        counts = np.zeros(num_regions, np.int64)
        psum = np.zeros(num_regions, np.float64)
        psumsq = np.zeros(num_regions, np.float64)
        for lo in range(0, len(ids), chunk_capacity):
            ids_c = ids[lo:lo + chunk_capacity]
            pw_c = pw[lo:lo + chunk_capacity]
            n_c = len(ids_c)
            if n_c < chunk_capacity:
                scratch_ids[:n_c] = ids_c
                scratch_ids[n_c:] = -1
                scratch_pw[:n_c] = pw_c
                scratch_pw[n_c:] = 0.0
                ids_c, pw_c = scratch_ids, scratch_pw
            c, s, sq = fn(ids_c, pw_c)
            # np.asarray blocks until the kernel has consumed its inputs,
            # so reusing the scratch on the next slice is safe.
            counts += np.asarray(c).astype(np.int64)[:num_regions]
            psum += np.asarray(s, np.float64)[:num_regions]
            psumsq += np.asarray(sq, np.float64)[:num_regions]
        return counts, psum, psumsq
    return agg


def make_carry_update(num_regions: int, *, use_pallas: bool | None = None,
                      block_n: int = DEFAULT_BLOCK_N,
                      block_r: int | None = None):
    """Traceable masked chunk→carry reduction for the fused device pipeline.

    Returns ``update(counts, psum, psumsq, ids, pows, valid)`` folding one
    fixed-shape chunk into the carry under a validity mask (lanes past the
    profiled horizon contribute nothing). Two carry layouts, dispatched
    on the carry rank at trace time:

    * scalar — ``psum``/``psumsq`` [R], ``pows`` [c]: the pre-rail
      reduction, kept graph-identical on purpose (D=1 bit-exactness;
      even value-equal graph variants reassociate under XLA fusion).
    * channels — ``psum``/``psumsq`` [R, C], ``pows`` [C, c]: one
      synchronized power reading per rail (+ total) per sample;
      ``counts`` stays [R] (every rail shares the sample clock).

    Carry dtypes are preserved — int64/float64 accumulation on CPU
    (under x64), the kernel's float32 per-chunk statistics added into
    the wider f64 carry on TPU.

    ``use_pallas`` defaults to backend dispatch: the Pallas one-hot matmul
    on TPU, an XLA scatter-add elsewhere (compiled, not interpret mode —
    interpret would put a Python loop back on the per-chunk path).

    Every variant runs under ``jax.named_scope("alea/reduce")``: the
    pipeline's stage name for the reduction in the compiled program's
    ``op_name`` metadata and the device trace.
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"

    if use_pallas:
        @jax.named_scope("alea/reduce")
        def update(counts, psum, psumsq, ids, pows, valid):
            ids_m = jnp.where(valid, ids, -1).astype(jnp.int32)
            if psum.ndim == 1:
                pw_m = jnp.where(valid, pows, 0.0).astype(jnp.float32)
                c, s, sq = sample_attr_pallas(ids_m, pw_m, num_regions,
                                              block_n=block_n,
                                              block_r=block_r,
                                              interpret=False)
                return (counts + c.astype(counts.dtype),
                        psum + s.astype(psum.dtype),
                        psumsq + sq.astype(psumsq.dtype))
            new_psum, new_psumsq = [], []
            c = None
            # One kernel launch per channel: the one-hot matmul reduces a
            # single power stream; rails are independent columns of the
            # same sample set (counts come from the first launch).
            for d in range(psum.shape[1]):
                pw_m = jnp.where(valid, pows[d], 0.0).astype(jnp.float32)
                cd, s, sq = sample_attr_pallas(
                    ids_m, pw_m, num_regions, block_n=block_n,
                    block_r=block_r, interpret=False)
                c = cd if c is None else c
                new_psum.append(s)
                new_psumsq.append(sq)
            return (counts + c.astype(counts.dtype),
                    psum + jnp.stack(new_psum, axis=1).astype(psum.dtype),
                    psumsq + jnp.stack(new_psumsq,
                                       axis=1).astype(psumsq.dtype))
        return update

    if num_regions <= 128:
        # Small region spaces: the same one-hot matmul the Pallas kernel
        # runs on the MXU, as one stacked [1 + 2C, c] @ [c, R] GEMM —
        # counts stay exact (integer-valued f64 sums), and XLA CPU
        # parallelizes dots where scatter is a serial loop.
        @jax.named_scope("alea/reduce")
        def update(counts, psum, psumsq, ids, pows, valid):
            ids_m = jnp.where(valid, ids, -1)
            onehot = (ids_m[:, None]
                      == jnp.arange(num_regions)[None, :]).astype(psum.dtype)
            if psum.ndim == 1:
                # Mask pw explicitly: the all-zero one-hot row alone
                # would turn a nonfinite masked-lane power into
                # 0·inf = NaN.
                pw = jnp.where(valid, pows, 0.0).astype(psum.dtype)
                stats = jnp.stack([valid.astype(psum.dtype), pw, pw * pw]) \
                    @ onehot
                return (counts + stats[0].astype(counts.dtype),
                        psum + stats[1], psumsq + stats[2])
            pw = jnp.where(valid[None, :], pows, 0.0).astype(psum.dtype)
            d = pw.shape[0]
            rows = jnp.concatenate(
                [valid.astype(psum.dtype)[None, :], pw, pw * pw])
            stats = rows @ onehot
            return (counts + stats[0].astype(counts.dtype),
                    psum + stats[1:1 + d].T, psumsq + stats[1 + d:].T)
        return update

    @jax.named_scope("alea/reduce")
    def update(counts, psum, psumsq, ids, pows, valid):
        # Invalid lanes scatter to index R, which is out of bounds for the
        # [R] carry and dropped — no branch, no extra dump slot to slice.
        idx = jnp.where(valid, ids, num_regions)
        pw = pows.astype(psum.dtype)
        counts = counts.at[idx].add(jnp.ones((), counts.dtype), mode="drop")
        if psum.ndim == 1:
            psum = psum.at[idx].add(pw, mode="drop")
            psumsq = psumsq.at[idx].add(pw * pw, mode="drop")
        else:
            psum = psum.at[idx].add(pw.T, mode="drop")
            psumsq = psumsq.at[idx].add((pw * pw).T, mode="drop")
        return counts, psum, psumsq
    return update
