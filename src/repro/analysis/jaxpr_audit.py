"""Layer 2 of the contract auditor: static walks over hot-path jaxprs.

The paper's ~1% overhead cap (and the RAPL-overhead study in PAPERS.md)
dies by a thousand cuts that unit tests don't see: an f64 op sneaking
into the serve decode step, a donated carry that silently stops
aliasing (doubling peak memory per step), a stray ``debug.print`` or
``pure_callback`` forcing a host sync per chunk. This module traces the
jitted hot paths *without running them* and reports, per path:

* an **f64-op inventory** (every equation producing a float64 output,
  by primitive) — ratcheted against ``x64_budget.json``: counts may
  only go down (ROADMAP item 2: drive the fused chunk step x64-free);
* **donation verification** — each ``donate_argnums`` entry must appear
  as an input-output alias (``tf.aliasing_output``) in the lowered
  StableHLO, otherwise the donation is a no-op and the step allocates
  a second carry;
* **host-callback / transfer detection** — callback primitives and
  implicit ``convert_element_type`` widenings to f64.

Audited paths: the device-pipeline region run and fused combo chunk
step at D=1 and D=3 (scalar vs multi-rail substrate), the miss-path
admit-or-fold scatter (``_combo_fold`` — the step bounded runs lean on
whenever the heavy-hitters tier folds tail combinations, so its carry
donation and f64 inventory are ratcheted like the steady-state step's),
the serve decode step for each KV-cache family (dense / MoE /
recurrent / hybrid), and the exchange collectives (psum all-reduce,
combination all-gather).
Path construction is shape-only where params would be large
(``jax.eval_shape``); nothing here compiles or executes device code
beyond tracing/lowering.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Callable, Iterable, Iterator, Sequence

import numpy as np

__all__ = [
    "JaxprStats", "PathReport", "iter_eqns", "audit_jaxpr",
    "count_aliased_outputs", "donation_of_jitted", "jit_cache_size",
    "HOT_PATH_BUILDERS", "audit_hot_paths",
]


# -- jaxpr walking ------------------------------------------------------------

def _as_open_jaxpr(j):
    """Accept ClosedJaxpr / Jaxpr / make_jaxpr output, duck-typed so we
    don't pin a jax.core layout."""
    inner = getattr(j, "jaxpr", None)
    return inner if inner is not None else j


def _sub_jaxprs(eqn) -> Iterator:
    for v in eqn.params.values():
        items = v if isinstance(v, (tuple, list)) else (v,)
        for item in items:
            if hasattr(item, "eqns"):                 # open Jaxpr
                yield item
            elif hasattr(item, "jaxpr") and hasattr(
                    getattr(item, "jaxpr"), "eqns"):  # ClosedJaxpr
                yield item.jaxpr


def iter_eqns(jaxpr) -> Iterator:
    """All equations of a (closed) jaxpr, recursing into call/control-flow
    sub-jaxprs (pjit, scan, while, cond branches, custom_jvp, ...)."""
    jaxpr = _as_open_jaxpr(jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub)


_CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "host_callback_call", "outside_call", "infeed", "outfeed",
    "debug_print",
})


def _is_callback_prim(name: str) -> bool:
    return name in _CALLBACK_PRIMS or "callback" in name


def _np_dtype(dt) -> np.dtype | None:
    try:
        return np.dtype(dt)
    except TypeError:
        return None      # extended dtype (PRNG key) — never float64


def _out_dtypes(eqn) -> Iterator[np.dtype]:
    for v in eqn.outvars:
        dt = _np_dtype(getattr(getattr(v, "aval", None), "dtype", None))
        if dt is not None:
            yield dt


def _in_dtypes(eqn) -> Iterator[np.dtype]:
    for v in eqn.invars:
        dt = _np_dtype(getattr(getattr(v, "aval", None), "dtype", None))
        if dt is not None:
            yield dt


@dataclasses.dataclass
class JaxprStats:
    """Static inventory of one traced computation."""
    eqn_count: int = 0
    f64_by_prim: dict = dataclasses.field(default_factory=dict)
    f64_widenings: int = 0
    callback_prims: list = dataclasses.field(default_factory=list)

    @property
    def f64_ops(self) -> int:
        return sum(self.f64_by_prim.values())

    @property
    def host_callbacks(self) -> int:
        return len(self.callback_prims)


_F64 = np.dtype(np.float64)


def audit_jaxpr(jaxpr) -> JaxprStats:
    """Walk every equation (recursively) and tally the inventory.

    An equation counts toward the f64 inventory when any output is
    float64. A ``convert_element_type`` whose output is float64 but
    whose input is not counts as a widening — the signature of an
    implicit promotion (weak-type contagion, a stray python float) as
    opposed to deliberate f64 arithmetic.
    """
    stats = JaxprStats()
    for eqn in iter_eqns(jaxpr):
        stats.eqn_count += 1
        name = eqn.primitive.name
        if _is_callback_prim(name):
            stats.callback_prims.append(name)
        out_f64 = any(dt == _F64 for dt in _out_dtypes(eqn))
        if out_f64:
            stats.f64_by_prim[name] = stats.f64_by_prim.get(name, 0) + 1
            if name == "convert_element_type" and not any(
                    dt == _F64 for dt in _in_dtypes(eqn)):
                stats.f64_widenings += 1
    return stats


# -- donation verification ----------------------------------------------------

_ALIAS_RE = re.compile(r"tf\.aliasing_output\s*=")


def count_aliased_outputs(stablehlo_text: str) -> int:
    """Input-output alias count in lowered StableHLO text. Donated args
    that XLA accepted carry a ``tf.aliasing_output = N`` attribute on
    the entry function's parameter."""
    return len(_ALIAS_RE.findall(stablehlo_text))


def donation_of_jitted(jitted, *args, expected: int, **kwargs
                       ) -> tuple[int, int]:
    """(expected, actually-aliased) for a jitted fn lowered at ``args``."""
    text = jitted.lower(*args, **kwargs).as_text()
    return expected, count_aliased_outputs(text)


# -- compile-cache introspection ----------------------------------------------

def jit_cache_size(fn) -> int:
    """Compiled-specialization count of a jitted callable — the probe
    behind the recompile-count guard (one (config, shape) key must mean
    exactly one compile)."""
    return int(fn._cache_size())


# -- hot-path registry --------------------------------------------------------

HOT_PATH_BUILDERS: dict[str, Callable[[], "PathReport"]] = {}


def _hot_path(name: str):
    def deco(fn):
        HOT_PATH_BUILDERS[name] = fn
        return fn
    return deco


@dataclasses.dataclass
class PathReport:
    """Audit result for one named hot path (the budget-file row)."""
    name: str
    eqn_count: int
    f64_ops: int
    f64_by_prim: dict
    f64_widenings: int
    host_callbacks: int
    callback_prims: tuple
    donated_expected: int = 0
    donated_aliased: int = 0

    @classmethod
    def from_stats(cls, name: str, stats: JaxprStats, *,
                   donated: tuple[int, int] = (0, 0)) -> "PathReport":
        return cls(name=name, eqn_count=stats.eqn_count,
                   f64_ops=stats.f64_ops,
                   f64_by_prim=dict(sorted(stats.f64_by_prim.items())),
                   f64_widenings=stats.f64_widenings,
                   host_callbacks=stats.host_callbacks,
                   callback_prims=tuple(stats.callback_prims),
                   donated_expected=donated[0], donated_aliased=donated[1])

    def render(self) -> str:
        parts = [f"{self.name}: {self.f64_ops} f64 ops"]
        if self.f64_by_prim:
            top = ", ".join(f"{k}×{v}" for k, v in
                            sorted(self.f64_by_prim.items(),
                                   key=lambda kv: -kv[1])[:4])
            parts.append(f"({top})")
        parts.append(f"{self.f64_widenings} widenings")
        parts.append(f"{self.host_callbacks} callbacks")
        if self.donated_expected:
            parts.append(f"donation {self.donated_aliased}/"
                         f"{self.donated_expected}")
        return ", ".join(parts)


# -- fixtures -----------------------------------------------------------------

_CHUNK = 256        # small audit chunk: same trace structure, fast


def _fixture_timelines(n: int, domains: bool):
    from repro.core.timeline import RegionCost, synthesize
    costs = [RegionCost("mem", flops=1e10, hbm_bytes=5e10, invocations=4),
             RegionCost("alu", flops=6e11, hbm_bytes=2e9, invocations=4),
             RegionCost("opt", flops=2e10, hbm_bytes=4e10, invocations=1)]
    return [synthesize(costs, steps=8, seed=s, domains=domains)
            for s in range(n)]


def _spec_for(tl):
    from repro.core.sensors import RaplTraceSensor
    return RaplTraceSensor.make_spec(domains=tl.domain_names)


def _region_audit(domains: bool) -> tuple:
    """(jaxpr stats,) of the fused single-worker region run."""
    import jax
    from jax import enable_x64

    from repro.core import device_pipeline as dp
    from repro.core.device_pipeline import DeviceTimeline

    (tl,) = _fixture_timelines(1, domains)
    spec = _spec_for(tl)
    dtl = DeviceTimeline.from_timelines([tl])
    with enable_x64():
        fn, args = dp.region_pipeline_call(dtl, spec, period=10e-3,
                                           chunk_size=_CHUNK,
                                           use_pallas=False)
        jaxpr = jax.make_jaxpr(fn)(*args)
    return (audit_jaxpr(jaxpr),)


@_hot_path("device_pipeline/region_run/d1")
def _region_d1() -> PathReport:
    (stats,) = _region_audit(domains=False)
    return PathReport.from_stats("device_pipeline/region_run/d1", stats)


@_hot_path("device_pipeline/region_run/d3")
def _region_d3() -> PathReport:
    (stats,) = _region_audit(domains=True)
    return PathReport.from_stats("device_pipeline/region_run/d3", stats)


def _combo_audit(domains: bool) -> tuple:
    """(stats, donation) of the fused multi-worker combo chunk step.

    Mirrors ``run_combo_pipeline``'s setup (W=2 workers, minimum table)
    and audits the steady-state step — including that all 5 carry
    leaves donate through to the step's carry output.
    """
    import jax
    import jax.numpy as jnp
    from jax import enable_x64

    from repro.core import device_pipeline as dp
    from repro.core.device_pipeline import DeviceTimeline
    from repro.core.streaming import CombinationInterner

    tls = _fixture_timelines(2, domains)
    spec = _spec_for(tls[0])
    dtl = DeviceTimeline.from_timelines(tls)
    pack = dp._pack_spec(dtl.num_regions, dtl.num_workers)
    n_chan = dp.num_channels(dtl.num_domains)
    cap = dp._TABLE_MIN
    with enable_x64():
        step = dp._combo_step_fn(_CHUNK, spec, dtl.grid_k, pack)
        table, table_ids, n_rows = dp._build_table(
            CombinationInterner(), cap, dtl.num_workers, pack)
        stat_shape = (cap,) if n_chan == 1 else (cap, n_chan)
        carry = (jnp.zeros(cap, jnp.int64),
                 jnp.zeros(stat_shape, jnp.float64),
                 jnp.zeros(stat_shape, jnp.float64),
                 jnp.zeros((), jnp.int64),
                 -jnp.ones((), jnp.float64))
        args = (carry, table, table_ids, n_rows, *dtl.arrays(),
                jax.ShapeDtypeStruct((_CHUNK,), jnp.float64),
                jnp.float64(dtl.t_end))
        jaxpr = jax.make_jaxpr(step)(*args)
        donated = donation_of_jitted(step, *args,
                                     expected=len(jax.tree.leaves(carry)))
    return audit_jaxpr(jaxpr), donated


@_hot_path("device_pipeline/combo_step/d1")
def _combo_d1() -> PathReport:
    stats, donated = _combo_audit(domains=False)
    return PathReport.from_stats("device_pipeline/combo_step/d1", stats,
                                 donated=donated)


@_hot_path("device_pipeline/combo_step/d3")
def _combo_d3() -> PathReport:
    stats, donated = _combo_audit(domains=True)
    return PathReport.from_stats("device_pipeline/combo_step/d3", stats,
                                 donated=donated)


def _fold_audit(domains: bool) -> tuple:
    """(stats, donation) of the miss-path admit-or-fold scatter.

    ``_combo_fold`` is the host-assisted half of every miss chunk: the
    recomputed per-sample channel powers scatter into the donated carry
    at host-resolved combination ids (padded with the out-of-bounds cap
    index). Bounded runs (``max_combinations``) take this path for all
    folded-tail traffic, so it is steady-state there — donation of the
    5 carry leaves must alias or peak memory doubles per miss chunk.
    """
    import jax
    import jax.numpy as jnp
    from jax import enable_x64

    from repro.core import device_pipeline as dp

    n_chan = dp.num_channels(3 if domains else 1)
    cap = dp._TABLE_MIN
    with enable_x64():
        stat_shape = (cap,) if n_chan == 1 else (cap, n_chan)
        carry = (jnp.zeros(cap, jnp.int64),
                 jnp.zeros(stat_shape, jnp.float64),
                 jnp.zeros(stat_shape, jnp.float64),
                 jnp.zeros((), jnp.int64),
                 -jnp.ones((), jnp.float64))
        pows = (jnp.zeros(_CHUNK, jnp.float64) if n_chan == 1
                else jnp.zeros((n_chan, _CHUNK), jnp.float64))
        args = (carry, jnp.full(_CHUNK, cap, jnp.int64), pows,
                jnp.zeros(_CHUNK, jnp.bool_))
        jaxpr = jax.make_jaxpr(dp._combo_fold)(*args)
        donated = donation_of_jitted(dp._combo_fold_jit, *args,
                                     expected=len(jax.tree.leaves(carry)))
    return audit_jaxpr(jaxpr), donated


@_hot_path("device_pipeline/combo_fold/d1")
def _fold_d1() -> PathReport:
    stats, donated = _fold_audit(domains=False)
    return PathReport.from_stats("device_pipeline/combo_fold/d1", stats,
                                 donated=donated)


@_hot_path("device_pipeline/combo_fold/d3")
def _fold_d3() -> PathReport:
    stats, donated = _fold_audit(domains=True)
    return PathReport.from_stats("device_pipeline/combo_fold/d3", stats,
                                 donated=donated)


# serve decode, one audit per KV-cache family (shape-only: params and
# cache come from jax.eval_shape, nothing is materialized).
_CACHE_FAMILIES = {
    "dense": "qwen3-1.7b",
    "moe": "qwen3-moe-30b-a3b",
    "ssm": "xlstm-125m",
    "hybrid": "zamba2-1.2b",
}


def _decode_audit(cfg_name: str) -> JaxprStats:
    import jax
    import jax.numpy as jnp

    from repro.configs.registry import get_config
    from repro.models import model as M

    cfg = get_config(cfg_name).reduced()
    B, T = 2, 16
    params = jax.eval_shape(
        lambda k: M.init_params(k, cfg), jax.random.PRNGKey(0))
    cache = jax.eval_shape(
        lambda: M.init_cache(cfg, B, T, dtype=jnp.bfloat16))
    tokens = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    cur_len = jax.ShapeDtypeStruct((B,), jnp.int32)
    mask = jax.ShapeDtypeStruct((B,), jnp.bool_)

    def decode(p, t, c, l, m):
        return M.decode_step(p, cfg, t, c, l, write_mask=m)

    jaxpr = jax.make_jaxpr(decode)(params, tokens, cache, cur_len, mask)
    return audit_jaxpr(jaxpr)


def _make_decode_path(family: str, cfg_name: str):
    @_hot_path(f"serve/decode/{family}")
    def _build() -> PathReport:
        return PathReport.from_stats(f"serve/decode/{family}",
                                     _decode_audit(cfg_name))
    return _build


for _family, _cfg in _CACHE_FAMILIES.items():
    _make_decode_path(_family, _cfg)


def _spec_audit(cfg_name: str, which: str) -> JaxprStats:
    """Self-speculative serving steps: the windowed draft (single token,
    StreamingLLM mask) and the multi-position verify. Shape-only, like
    the decode audit; L=4 matches the benchmark's headline cell. Neither
    step donates its cache (the window-start buffers are the rollback
    checkpoint — see ``serve.engine._jitted_spec_fns``), so their budget
    rows pin donation at 0/0, same as serve/decode."""
    import jax
    import jax.numpy as jnp

    from repro.configs.registry import get_config
    from repro.models import model as M

    cfg = get_config(cfg_name).reduced()
    B, T, L = 2, 16, 4
    params = jax.eval_shape(
        lambda k: M.init_params(k, cfg), jax.random.PRNGKey(0))
    cache = jax.eval_shape(
        lambda: M.init_cache(cfg, B, T, dtype=jnp.bfloat16))
    cur_len = jax.ShapeDtypeStruct((B,), jnp.int32)
    mask = jax.ShapeDtypeStruct((B,), jnp.bool_)

    if which == "draft":
        tokens = jax.ShapeDtypeStruct((B, 1), jnp.int32)

        def fn(p, t, c, l, m):
            return M.decode_step(p, cfg, t, c, l, write_mask=m,
                                 window=8, sinks=2)
    else:
        tokens = jax.ShapeDtypeStruct((B, L), jnp.int32)

        def fn(p, t, c, l, m):
            return M.decode_verify(p, cfg, t, c, l, write_mask=m)

    jaxpr = jax.make_jaxpr(fn)(params, tokens, cache, cur_len, mask)
    return audit_jaxpr(jaxpr)


def _make_spec_path(which: str, family: str, cfg_name: str):
    @_hot_path(f"serve/{which}/{family}")
    def _build() -> PathReport:
        return PathReport.from_stats(f"serve/{which}/{family}",
                                     _spec_audit(cfg_name, which))
    return _build


for _which in ("draft", "verify"):
    for _family, _cfg in _CACHE_FAMILIES.items():
        _make_spec_path(_which, _family, _cfg)


def _collective_audit(kind: str) -> JaxprStats:
    """Trace the shard_map'd exchange collective on a 1-host mesh."""
    import jax
    import jax.numpy as jnp
    from functools import partial
    from jax import enable_x64
    from jax.sharding import PartitionSpec as P

    from repro.core import exchange
    from repro.launch.mesh import make_exchange_mesh

    axis = "hosts"
    mesh = make_exchange_mesh(1, axis=axis)
    smap = partial(jax.shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(),
                   check_vma=False)
    cap, chan, width = 8, 3, 2
    with enable_x64():
        i64 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int64)
        f64 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float64)
        if kind == "region":
            fn = smap(exchange.region_allreduce_fn(axis))
            jaxpr = jax.make_jaxpr(fn)(
                i64(1, cap), f64(1, cap, chan), f64(1, cap, chan))
        else:
            fn = smap(exchange.combo_allgather_fn(axis))
            jaxpr = jax.make_jaxpr(fn)(
                i64(1, cap, width), i64(1, cap), f64(1, cap, chan),
                f64(1, cap, chan), i64(1, 1))
    return audit_jaxpr(jaxpr)


@_hot_path("exchange/collective/region_allreduce")
def _collective_region() -> PathReport:
    return PathReport.from_stats("exchange/collective/region_allreduce",
                                 _collective_audit("region"))


@_hot_path("exchange/collective/combo_allgather")
def _collective_combo() -> PathReport:
    return PathReport.from_stats("exchange/collective/combo_allgather",
                                 _collective_audit("combo"))


def audit_hot_paths(names: Sequence[str] | None = None
                    ) -> list[PathReport]:
    """Trace + audit the registered hot paths (all by default)."""
    if names is None:
        names = list(HOT_PATH_BUILDERS)
    unknown = [n for n in names if n not in HOT_PATH_BUILDERS]
    if unknown:
        raise KeyError(f"unknown hot paths: {unknown}; "
                       f"known: {sorted(HOT_PATH_BUILDERS)}")
    return [HOT_PATH_BUILDERS[n]() for n in names]
