"""GPipe-style pipeline parallelism over a ``pipe`` mesh axis.

For meshes with a pipeline axis (not the assigned production mesh — see
DESIGN.md §6), layers are partitioned into S stages; microbatches stream
through stages with ``jax.lax.ppermute`` boundary transfers inside a
``shard_map``. The schedule is the classic GPipe fill-drain loop: with M
microbatches and S stages, bubble fraction = (S−1)/(M+S−1).

Implementation notes (TPU-native): each device holds its stage's stacked
layer params; the loop body runs every stage in SPMD (devices compute
their own stage), then rotates activations one stage forward. Stage
assignment of layers is contiguous. Works with any per-layer block fn of
signature ``(params_i, x) -> x``.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


__all__ = ["pipeline_forward", "bubble_fraction"]


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipeline_forward(block_fn: Callable, mesh: Mesh, *, axis: str = "pipe",
                     n_micro: int):
    """Build a pipelined forward: (stage_params, x) → y.

    Args:
      block_fn: per-stage function ``(stage_params, x_micro) -> x_micro``;
        stage_params are the layers owned by one stage (leading dim =
        layers-per-stage, already sliced by shard_map).
      mesh: mesh containing ``axis``.
      n_micro: number of microbatches (global batch must divide).

    Returns a function ``f(params_stacked, x) -> y`` where
    ``params_stacked`` leaves have leading dim n_stages·layers_per_stage
    and x is [B, ...]; y is x after all stages, microbatched.
    """
    n_stages = mesh.shape[axis]

    def staged(params_local, x_local):
        # params_local: this stage's layers [L/S, ...]; x_local: the full
        # microbatch set [M, B/M, ...] (replicated over the pipe axis).
        stage = jax.lax.axis_index(axis)
        M = n_micro
        T = M + n_stages - 1          # schedule ticks

        def tick(carry, t):
            buf, out = carry          # buf: activation entering this stage
            # Which microbatch does stage 0 inject at tick t?
            mb_idx = jnp.clip(t, 0, M - 1)
            inject = x_local[mb_idx]
            cur = jnp.where(stage == 0, inject, buf)
            y = block_fn(params_local, cur)
            # Rotate stage s → s+1 (last stage's output is collected).
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            # Last stage emits microbatch (t - (S-1)) at ticks ≥ S-1.
            emit_idx = jnp.clip(t - (n_stages - 1), 0, M - 1)
            do_emit = jnp.logical_and(t >= n_stages - 1,
                                      stage == n_stages - 1)
            out = jnp.where(do_emit,
                            out.at[emit_idx].set(y), out)
            return (nxt, out), None

        buf0 = jnp.zeros_like(x_local[0])
        out0 = jnp.zeros_like(x_local)
        (buf, out), _ = jax.lax.scan(tick, (buf0, out0), jnp.arange(T))
        del buf
        # Only the last stage holds real outputs; broadcast them.
        out = jax.lax.psum(
            jnp.where(stage == n_stages - 1, out, jnp.zeros_like(out)),
            axis)
        return out

    def run(params_stacked, x):
        B = x.shape[0]
        assert B % n_micro == 0, (B, n_micro)
        xm = x.reshape(n_micro, B // n_micro, *x.shape[1:])
        f = jax.shard_map(
            staged, mesh=mesh,
            in_specs=(P(axis), P()),      # layers split over stages
            out_specs=P(),
            check_vma=False)
        out = f(params_stacked, xm)
        return out.reshape(B, *x.shape[1:])

    return run
