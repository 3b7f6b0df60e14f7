"""Top-k MoE with capacity-based expert-parallel dispatch.

Router (replicated math) runs in pjit-land; expert compute runs either:

  * locally (single process / smoke tests): all experts on one device;
  * under ``shard_map`` with experts sharded over the ``model`` mesh axis:
    every rank selects, for each of its local experts, the top-capacity
    tokens assigned to that expert, runs the expert FFN on the gathered
    slab, scatter-adds weighted outputs, and a single ``psum`` over the
    expert axis combines contributions — an allreduce-combine EP scheme.
    (The all-to-all dispatch variant is a §Perf hillclimb alternative —
    see ``moe_apply_a2a``.)

Tokens beyond an expert's capacity are dropped (standard capacity-factor
semantics); dropped tokens pass through on the residual path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.regions import region
from repro.models.layers import Params, dense_init
from repro.sharding.rules import constrain, current_rules

__all__ = ["moe_init", "moe_ffn", "router"]


def moe_init(key, cfg: ModelConfig) -> Params:
    d, E, ff = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], d, E),
        "up": jax.vmap(lambda k: dense_init(k, d, ff))(
            jax.random.split(ks[1], E)),
        "gate": jax.vmap(lambda k: dense_init(k, d, ff))(
            jax.random.split(ks[2], E)),
        "down": jax.vmap(lambda k: dense_init(k, ff, d))(
            jax.random.split(ks[3], E)),
    }


def router(p: Params, cfg: ModelConfig, x: jnp.ndarray):
    """x: [T,d] → (combine_weights [T,k], expert_idx [T,k], aux_loss)."""
    logits = (x.astype(jnp.float32) @ p["router"])          # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg.top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance auxiliary loss.
    E = cfg.n_experts
    assign = jax.nn.one_hot(top_i[:, 0], E, dtype=jnp.float32)
    f = assign.mean(0)                  # dispatch fraction per expert
    pr = probs.mean(0)                  # mean router prob per expert
    aux = cfg.router_aux_coeff * E * jnp.sum(f * pr)
    return top_p, top_i, aux


def _expert_compute(up, gate, down, x_slab):
    """Batched expert FFN. x_slab: [El, C, d] → [El, C, d]."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x_slab, gate.astype(x_slab.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", x_slab, up.astype(x_slab.dtype))
    return jnp.einsum("ecf,efd->ecd", h, down.astype(x_slab.dtype))


def _dispatch_dense(up, gate, down, x, top_p, top_i):
    """Dropless per-token dispatch: gather each token's top-k experts'
    weights and run them directly — T·k expert-rows of compute instead
    of the capacity path's E·T (which, at dropless capacity, runs every
    expert over every token and zero-weights the misses). The gather
    reads at most T·k experts' weights; decode-sized T makes that far
    below the capacity path's all-E read.
    """
    gu = jnp.take(up, top_i, axis=0)          # [T, k, d, ff]
    gg = jnp.take(gate, top_i, axis=0)
    gd = jnp.take(down, top_i, axis=0)        # [T, k, ff, d]
    h = jax.nn.silu(jnp.einsum("td,tkdf->tkf", x, gg.astype(x.dtype)))
    h = h * jnp.einsum("td,tkdf->tkf", x, gu.astype(x.dtype))
    h = h * top_p[..., None].astype(x.dtype)
    return jnp.einsum("tkf,tkfd->td", h, gd.astype(x.dtype))


def _dispatch_local(up, gate, down, x, top_p, top_i, *, e0: int,
                    n_local: int, n_total: int, capacity: int):
    """Capacity-gather dispatch for experts [e0, e0+n_local).

    x: [T,d]; top_p/top_i: [T,k]. Returns partial y [T,d] containing only
    the local experts' contributions (caller psums across expert shards).
    """
    T = x.shape[0]
    # score[e_local, t]: combine weight if token t routed to local expert e.
    local_ids = e0 + jnp.arange(n_local)                       # [El]
    match = (top_i[None, :, :] == local_ids[:, None, None])    # [El, T, k]
    score = jnp.where(match, top_p[None, :, :], 0.0).sum(-1)   # [El, T]
    # Per-expert top-capacity token selection (tokens over capacity drop).
    cap = min(capacity, T)
    w, tok_idx = jax.lax.top_k(score, cap)                     # [El, C]
    x_slab = jnp.take(x, tok_idx.reshape(-1), axis=0)          # [El*C, d]
    x_slab = x_slab.reshape(n_local, cap, -1)
    y_slab = _expert_compute(up, gate, down, x_slab)           # [El, C, d]
    y_slab = y_slab * w[..., None].astype(y_slab.dtype)
    y = jnp.zeros_like(x)
    y = y.at[tok_idx.reshape(-1)].add(y_slab.reshape(n_local * cap, -1))
    return y


def moe_ffn(p: Params, cfg: ModelConfig, x: jnp.ndarray, *,
            dropless: bool = False):
    """MoE FFN over x: [B,S,d] (or [T,d]). Returns (y, aux_loss).

    ``dropless=True`` guarantees no token is ever dropped. Decode paths
    use it: the capacity heuristic is a load-balancing device calibrated
    for training-scale T, and at decode batch sizes it quantizes to ~1
    slot — making each slot's output depend on which *other* requests
    share the batch (a dropped token silently degrades to its residual).
    Dropless dispatch keeps every row's computation row-local, so
    continuous batching is token-exact against single-request decoding.
    Local (unsharded) dropless routes through :func:`_dispatch_dense`
    (T·k expert-rows); the expert-parallel path keeps the capacity
    gather with capacity = local token count (dense gather would need
    cross-shard expert weights).
    """
    orig_shape = x.shape
    x2 = x.reshape(-1, orig_shape[-1])
    with region("moe_router"):
        top_p, top_i, aux = router(p, cfg, x2)
    E = cfg.n_experts

    rules = current_rules()
    expert_axis = None if rules is None else rules.mapping.get("experts")
    if expert_axis is None or rules.mesh is None:
        if dropless:
            with region("moe_ffn"):
                y = _dispatch_dense(p["up"], p["gate"], p["down"], x2,
                                    top_p, top_i)
            return y.reshape(orig_shape), aux
        cap = max(int(cfg.capacity_factor * x2.shape[0] * cfg.top_k / E), 1)
        with region("moe_ffn"):
            y = _dispatch_local(p["up"], p["gate"], p["down"], x2,
                                top_p.astype(x2.dtype), top_i,
                                e0=0, n_local=E, n_total=E, capacity=cap)
        return y.reshape(orig_shape), aux

    mesh = rules.mesh
    n_shards = mesh.shape[expert_axis]
    assert E % n_shards == 0, (E, n_shards)
    n_local = E // n_shards
    batch_axes = rules.mapping.get("batch")

    # Per-DP-shard token count sets capacity (tokens are sharded over DP
    # axes and replicated over the expert axis inside the shard_map block).
    dp = 1
    if batch_axes is not None:
        for a in ((batch_axes,) if isinstance(batch_axes, str) else batch_axes):
            dp *= mesh.shape[a]
    t_local = max(x2.shape[0] // dp, 1)
    cap = t_local if dropless else max(
        int(cfg.capacity_factor * t_local * cfg.top_k / E), 1)

    bspec = batch_axes if batch_axes is not None else None
    tok_spec = P(bspec, None)       # [T, d] with T sharded over DP axes
    rt_spec = P(bspec, None)

    def wrapped(xl, pl, il, up, gate, down):
        e0 = jax.lax.axis_index(expert_axis) * n_local
        y = _dispatch_local(up, gate, down, xl, pl.astype(xl.dtype), il,
                            e0=e0, n_local=n_local, n_total=E, capacity=cap)
        return jax.lax.psum(y, expert_axis)

    with region("moe_ffn"):
        y2 = jax.shard_map(
            wrapped, mesh=mesh,
            in_specs=(tok_spec, rt_spec, rt_spec,
                      P(expert_axis, None, None), P(expert_axis, None, None),
                      P(expert_axis, None, None)),
            out_specs=tok_spec,
            check_vma=False,
        )(x2, top_p, top_i, p["up"], p["gate"], p["down"])
    return y2.reshape(orig_shape), aux
