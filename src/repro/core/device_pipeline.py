"""Device-resident fused sampling→attribution pipeline (ALEA hot path).

The host streaming path (``sampler.iter_sample_chunks`` →
``StreamingAggregator``) bounces every chunk across the host↔device
boundary: numpy ``region_at``/sensor emulation on host, kernel attribution
on device, accumulation back on host — and the multi-worker variant adds
an O(W) Python loop per chunk. This module moves the whole per-chunk loop
onto the device:

* :class:`DeviceTimeline` — the sampling substrate resident on device:
  interval ``ends``, the cumulative energy integral, ``powers`` and
  ``region_ids``, batched ``[W, m]`` (ragged workers padded, per-worker
  valid length carried alongside).

* **Counter-based sample times** — chunk ``k``'s times are a pure function
  of ``(seed, k)``: ``t_i = u0 + i·T + u_i`` with ``u0 ~ U(0, T)``,
  ``u_i ~ U(0, jitter)`` drawn from ``fold_in(key, k+1)`` (threefry), and
  the result quantized to an integer-nanosecond clock. Chunk ``k`` is
  reproducible with no host state — the carry never includes a time
  cursor. (Deviation from the host process: jitter is per-sample rather
  than accumulated — statistically equivalent protection against phase
  locking at realistic jitter, and the price of statelessness.)

* **Fused chunk step** — one jitted fixed-shape step per chunk: time
  generation, vectorized region lookup (``searchsorted(side="right")``
  semantics: a per-worker time grid bounds each count to a window of
  ``grid_k + 1`` candidates, which a ``ceil(log2(grid_k + 1))``-step
  binary search resolves; ``vmap`` over the worker axis), trace-sensor
  emulation as pure functions of the energy integral (RAPL differencing
  with a one-scalar prev-sample carry, INA231 window semantics), and the
  ``sample_attr`` reduction folding into a donated ``(counts, Σpow,
  Σpow²)`` carry
  (:func:`repro.kernels.sample_attr.ops.make_carry_update`: Pallas one-hot
  matmuls on TPU, XLA scatter-add elsewhere). Chunk padding/masking
  happens *inside* the step (lanes past the profiled horizon scatter out
  of bounds and drop) — no host-side ``np.concatenate`` padding.
  Each stage of the step runs under a ``jax.named_scope``, so the
  compiled program's ``op_name`` metadata, and hence a device trace,
  names the stage of every operation: ``alea/clock`` (sample times, their
  mask and clip), ``alea/lookup`` (every ``#(ends ≤ t)`` count),
  ``alea/sensor`` (region-id gather, sensor emulation, channel sums,
  idle blend) and ``alea/reduce`` (the carry update). Scopes nest; the
  innermost ``alea/`` one names the operation. They are metadata only:
  the computation graph is the same with or without them.

* :func:`run_region_pipeline` — single-worker runs execute the whole scan
  in ONE jitted ``fori_loop``: no per-chunk dispatch, no per-chunk host
  transfer; only the final sufficient statistics come back.

* :func:`run_combo_pipeline` — multi-worker (§4.4) combination
  attribution with a device-resident, lexicographically sorted combination
  key table. Chunks whose rows all hit the table fold entirely on device
  (binary search → interner ids → scatter into the donated carry). A
  chunk containing an unseen combination raises a scalar miss flag; only
  then does the host pull that one chunk, intern the new rows
  (:class:`~repro.core.streaming.CombinationInterner` — the id space stays
  host-authoritative because it is dynamic and ordered), rebuild the
  sorted table, and fold the chunk through a fixed-shape device update.
  Each chunk's sample times are drawn on the host's CPU
  (:func:`_ref_times`, IEEE float64) and uploaded, so every worker's
  lookup sees the contract's exact nanoseconds on a device that emulates
  float64. Once the combination set is stable no other sample array is
  transferred; on SPMD traffic, where skew and jitter keep making new
  combinations, every chunk misses (see :func:`run_combo_pipeline`). The
  key packing and table search run under ``alea/combo_key``, the scatters
  under ``alea/combo_fold``, and the host's miss path under the host trace
  spans ``alea/miss`` (``/recompute``, ``/intern``, ``/table``).

Everything runs under ``enable_x64`` (cf. :mod:`repro.core.exchange`):
float64 times make device region lookups bit-identical to the numpy
reference, and int64/float64 accumulators keep the statistics exact on
CPU. The numpy reference (:func:`reference_region_pipeline` /
:func:`reference_combo_pipeline`) consumes the same
:func:`chunk_sample_times` and mirrors the sensor math in float64 — the
oracle the equivalence tests pin the fused path against.

**Power-rail domain axis.** Multi-domain timelines (``Timeline.domains``
— package/HBM/ICI rails) thread end to end: the substrate carries
per-rail energy integrals, the sensor bank is vmapped over the domain
axis (one interval lookup serves every rail — they share the clock),
and the carry accumulates a ``[rows, C]`` channel matrix (the D rails
plus a dedicated total channel; Σpow² of the total is not derivable
from per-rail Σpow², see :func:`num_channels`). Scalar timelines keep
1-D statistics through the *verbatim* pre-rail computation graph —
the D=1 bit-exactness contract, pinned by golden-value tests
(``tests/test_domains.py``).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax import enable_x64

from repro.core.sensors import (DEFAULT_IDLE_POWER, SensorSpec,
                                _TraceSensorBase, idle_channel)
from repro.core.sketch import SketchConfigError, other_row
from repro.core.streaming import (CombinationInterner,
                                  StreamingCombinationAggregator,
                                  channels_for)
from repro.core.timeline import Timeline
from repro.kernels.sample_attr.ops import make_carry_update

__all__ = [
    "DeviceTimeline", "PipelineResult", "chunk_sample_times",
    "num_chunks", "num_channels", "region_pipeline_call",
    "run_region_pipeline",
    "run_combo_pipeline", "reference_region_pipeline",
    "reference_combo_pipeline",
]

DEFAULT_CHUNK = 65536
_TABLE_MIN = 64


# ---------------------------------------------------------------------------
# Device timeline substrate.
# ---------------------------------------------------------------------------


_GRID_OVERSAMPLE = 4        # grid cells per interval (amortizes window K)
_GRID_MAX = 1 << 20


@dataclasses.dataclass(frozen=True)
class DeviceTimeline:
    """Device-resident piecewise-constant traces, batched over workers.

    Ragged workers are padded to a common interval count ``M``: ``ends``
    and ``bounds`` pad with ``+inf`` (lookups never land there for
    in-horizon times), value arrays pad with zeros, and ``m_true`` carries
    each worker's valid interval count so lookups clip per worker exactly
    like the host path clips to its own length.

    The power substrate is per-rail: for multi-domain timelines
    ``powers``/``eint`` carry a domain axis ``[W, D, ·]`` (package/HBM/
    ICI rails). Scalar (D=1) timelines keep the flat ``[W, ·]`` layout —
    deliberately: the jitted pipeline branches on the array rank at
    trace time and runs the *identical* pre-rail computation graph for
    scalar substrates, which is what makes D=1 outputs bit-exact (XLA's
    whole-graph fusion reassociates float reductions at the ulp level
    if the same math merely flows through differently-shaped arrays).
    Interval *structure* (ends/bounds/region ids and the grid
    accelerator) never has a domain axis: all rails of a worker share
    one clock, so one interval lookup serves every channel.

    ``grid``/``cell``/``grid_k`` form the lookup accelerator: per worker,
    ``grid[g] = #(ends ≤ g·cell)`` on a uniform time grid, with ``grid_k``
    the maximum interval count of any cell. A time in cell ``g`` counts
    between ``grid[g]`` and ``grid[g] + grid_k`` ends, so an interval
    lookup is one grid gather plus a binary search of that window in
    :attr:`lookup_steps` ``= ceil(log2(grid_k + 1))`` gathers — exactly
    ``searchsorted(side="right")``, at a cost set by the densest cell
    instead of by ``log2(m)`` (the device hot path's dominant cost).
    Bursty timelines only widen the window; since ``grid_k ≤ m`` it never
    takes more steps than a search of every end. Because
    ``bounds = [0, ends...]``, the energy-interpolation index derives from
    the same count: ``#(bounds ≤ t) = 1 + #(ends ≤ t)`` — one structure
    accelerates both lookups.
    """

    ends: jax.Array        # f64 [W, M]   interval end times, +inf padded
    bounds: jax.Array      # f64 [W, M+1] [0, ends...], +inf padded
    eint: jax.Array        # f64 [W, M+1] (D=1) | [W, D, M+1] rail energy
    powers: jax.Array      # f64 [W, M] (D=1) | [W, D, M] rail powers, 0 pad
    region_ids: jax.Array  # i32 [W, M]   region per interval, 0 padded
    m_true: jax.Array      # i32 [W]      valid interval count per worker
    grid: jax.Array        # i32 [W, G+2] #(ends ≤ g·cell) per grid point
    cell: jax.Array        # f64 [W]      grid cell width (span / G)
    grid_k: int            # static: max intervals per grid cell
    t_end: float           # profiled horizon: min worker t_exec
    num_regions: int
    names: tuple[str, ...]
    domains: tuple[str, ...] = ("total",)   # rail axis names

    @property
    def num_workers(self) -> int:
        return self.ends.shape[0]

    @property
    def num_domains(self) -> int:
        return len(self.domains)

    @property
    def lookup_steps(self) -> int:
        """Binary-search steps of one interval lookup: ``ceil(log2(grid_k
        + 1))``, enough to resolve a cell's window of ``grid_k + 1``
        possible counts."""
        return _search_steps(self.grid_k)

    @classmethod
    def from_timelines(cls, timelines: list[Timeline]) -> "DeviceTimeline":
        if not timelines:
            raise ValueError("need at least one timeline")
        names = timelines[0].names
        domains = timelines[0].domain_names
        for tl in timelines:
            if tl.names != names:
                raise ValueError("workers must share a region name space")
            if tl.domain_names != domains:
                raise ValueError(
                    f"workers must share a power-rail domain axis; got "
                    f"{tl.domain_names} vs {domains}")
            if len(tl.region_ids) == 0:
                raise ValueError("empty timeline")
            if tl.t_exec <= 0.0:
                raise ValueError("zero-length timeline")
        W = len(timelines)
        D = len(domains)
        M = max(len(tl.region_ids) for tl in timelines)
        G = int(min(_GRID_OVERSAMPLE * M, _GRID_MAX))
        ends = np.full((W, M), np.inf)
        bounds = np.full((W, M + 1), np.inf)
        # Scalar timelines keep the flat pre-rail layout (see class
        # docstring: the bit-exactness contract hangs on it).
        eint = np.zeros((W, M + 1) if D == 1 else (W, D, M + 1))
        powers = np.zeros((W, M) if D == 1 else (W, D, M))
        rids = np.zeros((W, M), np.int32)
        m_true = np.array([len(tl.region_ids) for tl in timelines], np.int32)
        grid = np.zeros((W, G + 2), np.int32)
        cell = np.zeros(W)
        grid_k = 1
        for w, tl in enumerate(timelines):
            m = int(m_true[w])
            ends[w, :m] = tl.ends
            bounds[w, 0] = 0.0
            bounds[w, 1:m + 1] = tl.ends
            if D == 1:
                eint[w, 1:m + 1] = tl.energy_integral()
                powers[w, :m] = tl.powers
            else:
                eint[w, :, 1:m + 1] = tl.rail_energy_integral().T
                powers[w, :, :m] = tl.rails().T
            rids[w, :m] = tl.region_ids
            cell[w] = tl.t_exec / G
            # Same f64 products the device guard computes (g · cell), so
            # grid[g] is exact for the comparisons the lookup performs.
            pts = np.arange(G + 2, dtype=np.float64) * cell[w]
            grid[w] = np.searchsorted(tl.ends, pts, side="right")
            grid_k = max(grid_k, int(np.diff(grid[w]).max()))
        with enable_x64():
            return cls(ends=jnp.asarray(ends), bounds=jnp.asarray(bounds),
                       eint=jnp.asarray(eint), powers=jnp.asarray(powers),
                       region_ids=jnp.asarray(rids),
                       m_true=jnp.asarray(m_true),
                       grid=jnp.asarray(grid), cell=jnp.asarray(cell),
                       grid_k=grid_k,
                       t_end=float(min(tl.t_exec for tl in timelines)),
                       num_regions=len(names), names=names,
                       domains=domains)

    def arrays(self):
        return (self.ends, self.bounds, self.eint, self.powers,
                self.region_ids, self.m_true, self.grid, self.cell)


@dataclasses.dataclass(frozen=True)
class PipelineResult:
    """Final sufficient statistics of one fused run (host numpy).

    ``psum``/``psumsq`` are the scalar (rail-summed) statistics — for
    D=1 runs the single rail itself, bit-identical to the pre-rail
    pipeline. ``rail_psum``/``rail_psumsq`` carry the per-domain
    decomposition ``[R, D]`` aligned with ``domains``.
    """

    counts: np.ndarray     # int64 [R]
    psum: np.ndarray       # float64 [R]
    psumsq: np.ndarray     # float64 [R]
    n: int                 # total valid samples
    t_exec: float          # measured horizon incl. suspension overhead
    rail_psum: np.ndarray | None = None     # float64 [R, D]
    rail_psumsq: np.ndarray | None = None   # float64 [R, D]
    domains: tuple[str, ...] = ("total",)


def num_channels(num_domains: int) -> int:
    """Statistic channels for a D-rail run — delegates to the one
    channel-layout rule (:func:`repro.core.streaming.channels_for`):
    the rails plus, when D > 1, a dedicated total-power channel (Σpow²
    of the total is not derivable from per-rail Σpow²). At D = 1 the
    single rail is the total, bit-identical to the pre-rail carry."""
    return channels_for(num_domains)


def _result_from_channels(counts, chan_psum, chan_psumsq, n, t_exec,
                          domains) -> PipelineResult:
    """Split a channel carry into (rail, scalar-total) statistics.

    Accepts the scalar-path 1-D carry (D = 1) or the [R, C] channel
    carry; the last channel is the total (at D = 1 it is also the only
    rail), so ``psum``/``psumsq`` are exactly the scalar accumulators."""
    chan_psum = np.asarray(chan_psum, np.float64)
    chan_psumsq = np.asarray(chan_psumsq, np.float64)
    if chan_psum.ndim == 1:
        chan_psum = chan_psum[:, None]
        chan_psumsq = chan_psumsq[:, None]
    d = len(domains)
    return PipelineResult(counts=np.asarray(counts, np.int64),
                          psum=chan_psum[:, -1], psumsq=chan_psumsq[:, -1],
                          n=n, t_exec=t_exec,
                          rail_psum=chan_psum[:, :d],
                          rail_psumsq=chan_psumsq[:, :d],
                          domains=tuple(domains))


# ---------------------------------------------------------------------------
# Counter-based sample times (the chunk-step contract's time source).
# ---------------------------------------------------------------------------


@jax.named_scope("alea/clock")
def _raw_chunk_times(root, k, c: int, period, jitter):
    """Chunk ``k``'s sample times: pure function of (key, k).

    ``t_i = u0 + i·T + u_i`` on an integer-nanosecond clock. The ns
    quantization is part of the contract: it models a real timer's
    resolution and pins the float64 value exactly, so the numpy reference
    recovers identical region lookups. Exactly in IEEE float64: where the
    device emulates float64 (the TPU), a time can round to the
    neighbouring nanosecond.
    """
    dt = period.dtype
    u0 = jax.random.uniform(jax.random.fold_in(root, 0), (), dt, 0.0, period)
    u = jax.random.uniform(jax.random.fold_in(root, k + 1), (c,), dt,
                           0.0, jitter)
    # k arrives as int32 (fori_loop index); widen BEFORE k·c so sample
    # indices past 2^31 (long runs at small chunk sizes) don't wrap.
    i = jnp.asarray(k, jnp.int64) * c + jnp.arange(c)
    t = u0 + i.astype(dt) * period + u
    return jnp.floor(t * 1e9 + 0.5) * 1e-9


@functools.partial(jax.jit, static_argnames=("chunk_size",))
def chunk_sample_times(root, k, period, jitter, *, chunk_size: int):
    """Public (jitted) form of the time contract — the reference oracle
    consumes exactly these times, so time generation is shared, not
    re-derived, between the fused path and its numpy mirror."""
    return _raw_chunk_times(root, k, chunk_size, period, jitter)


def num_chunks(t_end: float, period: float, chunk_size: int) -> int:
    """Chunks needed to cover the horizon: ``t_i ≥ i·T`` guarantees every
    sample of chunk ``k ≥ ceil(t_end/(c·T))`` lands past ``t_end``."""
    return max(int(math.ceil(t_end / (chunk_size * period))), 1)


# ---------------------------------------------------------------------------
# Device lookups + trace-sensor emulation (pure functions of the integral).
# ---------------------------------------------------------------------------


def _search_steps(grid_k: int) -> int:
    """``ceil(log2(grid_k + 1))``: the halvings that resolve a window of
    ``grid_k + 1`` possible counts."""
    return int(grid_k).bit_length()


@jax.named_scope("alea/lookup")
def _count_le(ends_w, grid_w, cell_w, t, grid_k: int):
    """``#(ends ≤ t)`` per sample — ``searchsorted(side="right")``, but
    bounded by the precomputed grid: locate the cell (with exact-comparison
    guards against division rounding); its count lies in ``[grid[g],
    grid[g] + grid_k]``, so a branchless binary search from ``grid[g]``
    with steps ``2^(S-1), …, 2, 1`` (``S = ceil(log2(grid_k + 1))``, their
    sum ``2^S - 1 ≥ grid_k``) resolves it. ``ends`` are sorted and padded
    with ``+inf``; only integer arithmetic and exact compares are
    involved, so this is bit-equal to the numpy reference's
    searchsorted."""
    G = grid_w.shape[0] - 2
    g = jnp.floor(t / cell_w).astype(jnp.int32)
    g = g - (g * cell_w > t)
    g = g + ((g + 1) * cell_w <= t)
    g = jnp.clip(g, 0, G)
    M = ends_w.shape[0]
    cnt = grid_w[g]
    for j in reversed(range(_search_steps(grid_k))):
        pos = cnt + ((1 << j) - 1)
        take = (pos < M) & (ends_w[jnp.minimum(pos, M - 1)] <= t)
        cnt = cnt + jnp.where(take, 1 << j, 0)
    return cnt


def _energy_at_cnt(bounds_w, eint_w, powers_w, m_w, x, cnt):
    """Exact E(x) for piecewise-constant power (device twin of
    ``sensors._TraceSensorBase._energy_at``) given ``cnt = #(ends ≤ x)``;
    ``bounds = [0, ends...]`` makes the bounds index ``clip(cnt)``."""
    idx = jnp.clip(cnt, 0, m_w - 1)
    return eint_w[idx] + (x - bounds_w[idx]) * powers_w[idx]


@jax.named_scope("alea/sensor")
def _sensor_powers(spec: SensorSpec, arrs, t, cnt_t, valid, prev,
                   grid_k: int):
    """Per-worker sensor readings + updated RAPL prev-sample carry.

    Scalar substrates (``powers`` [W, M]) return [W, c] — the verbatim
    pre-rail computation graph, which is what keeps D=1 outputs
    bit-identical. Multi-rail substrates (``powers`` [W, D, M]) return
    [W, D, c]: the sensor bank is vmapped over the domain axis — every
    rail applies the same instrument semantics to its own energy
    integral, sharing the worker's interval lookup (``cnt_t`` [W, c]:
    rails share the clock and the interval structure, so one count
    serves all channels). ``prev`` is a single f64 scalar (< 0 means
    "no sample taken yet"): all workers and rails share the sample
    clock, so the RAPL differencing chain has one prev time regardless
    of W or D.
    """
    ends, bounds, eint, powers, rids, m_true, grid, cell = arrs
    scalar = powers.ndim == 2
    count = jax.vmap(_count_le, in_axes=(0, 0, 0, None, None))
    if scalar:
        e_at = jax.vmap(_energy_at_cnt, in_axes=(0, 0, 0, 0, None, 0))
    else:
        # Inner vmap batches the domain axis of eint/powers (bounds,
        # valid length and the sample count are per worker, shared by
        # its rails); outer vmap batches workers.
        e_at_d = jax.vmap(_energy_at_cnt,
                          in_axes=(None, 0, 0, None, None, None))
        e_at = jax.vmap(e_at_d, in_axes=(0, 0, 0, 0, None, 0))
    if spec.kind == "instant":
        if scalar:
            def one(p_w, m_w, cnt_w):
                return p_w[jnp.clip(cnt_w, 0, m_w - 1)]
        else:
            def one(p_w, m_w, cnt_w):
                return p_w[:, jnp.clip(cnt_w, 0, m_w - 1)]
        return jax.vmap(one)(powers, m_true, cnt_t), prev
    if spec.kind == "rapl":
        up = spec.update_period
        tq = jnp.floor(t / up + 1e-6) * up
        # The prev chain is tq shifted by one sample, so E(prev) is e_q
        # shifted by one lane — one energy pass instead of two; only the
        # chain head (carry prev, or tq[0] - up on the very first sample)
        # needs its own tiny lookup.
        prev0 = jnp.where(prev < 0.0, jnp.maximum(tq[0] - up, 0.0), prev)
        e_q = e_at(bounds, eint, powers, m_true, tq,
                   count(ends, grid, cell, tq, grid_k))
        e_p0 = e_at(bounds, eint, powers, m_true, prev0[None],
                    count(ends, grid, cell, prev0[None], grid_k))
        e_prev = jnp.concatenate([e_p0, e_q[..., :-1]], axis=-1)
        prev_vec = jnp.concatenate([prev0[None], tq[:-1]])
        dt = jnp.maximum(tq - prev_vec, up)
        new_prev = jnp.max(jnp.where(valid, tq, -jnp.inf))
        new_prev = jnp.where(jnp.any(valid), new_prev, prev)
        return (e_q - e_prev) / dt, new_prev
    if spec.kind == "ina231":
        lo = jnp.maximum(t - spec.window, 0.0)
        e_t = e_at(bounds, eint, powers, m_true, t, cnt_t)
        e_lo = e_at(bounds, eint, powers, m_true, lo,
                    count(ends, grid, cell, lo, grid_k))
        return (e_t - e_lo) / jnp.maximum(t - lo, 1e-12), prev
    raise ValueError(f"unknown trace sensor kind: {spec.kind!r}")


def _chunk_samples(arrs, spec: SensorSpec, root, k, c: int, period, jitter,
                   t_end, prev, grid_k: int):
    """One fused chunk: times → region ids [W, c] → channel powers.

    Scalar substrates produce the summed power [c] (the pre-rail graph);
    multi-rail substrates produce the [C, c] channel matrix — the
    worker-summed rails plus the total (see :func:`num_channels`).
    Masking happens here, in the kernel's input domain: lanes past the
    horizon are flagged invalid and their times clipped to ``t_end`` so
    the sensor math stays finite (they contribute nothing downstream).
    """
    return _samples_at(arrs, spec, _raw_chunk_times(root, k, c, period,
                                                    jitter),
                       t_end, prev, grid_k)


def _samples_at(arrs, spec: SensorSpec, t_raw, t_end, prev, grid_k: int):
    """:func:`_chunk_samples` at the sample times ``t_raw``."""
    ends, bounds, eint, powers, rids, m_true, grid, cell = arrs
    with jax.named_scope("alea/clock"):
        valid = t_raw < t_end
        t = jnp.minimum(t_raw, t_end)
    cnt_t = jax.vmap(_count_le, in_axes=(0, 0, 0, None, None))(
        ends, grid, cell, t, grid_k)

    def lookup(r_w, m_w, cnt_w):
        return r_w[jnp.clip(cnt_w, 0, m_w - 1)]
    with jax.named_scope("alea/sensor"):
        rid_mat = jax.vmap(lookup)(rids, m_true, cnt_t)
        pows, prev = _sensor_powers(spec, arrs, t, cnt_t, valid, prev,
                                    grid_k)
        chan = pows.sum(axis=0)              # [c] scalar | [D, c] rails
        if chan.ndim == 2:
            chan = jnp.concatenate([chan, chan.sum(axis=0, keepdims=True)])
    return rid_mat, chan, valid, prev


def _check_sampling_args(spec: SensorSpec, period: float, jitter: float):
    if period < spec.effective_min_period():
        raise ValueError(f"sampling period {period} below sensor minimum "
                         f"{spec.effective_min_period()}")
    if jitter > period:
        raise ValueError(
            f"device pipeline requires jitter <= period for a monotone "
            f"sample clock (RAPL differencing); got jitter={jitter}, "
            f"period={period}")


def _check_spec_domains(spec: SensorSpec, dtl: "DeviceTimeline"):
    """The sensor bank must have one channel per timeline rail."""
    if spec.num_domains != dtl.num_domains:
        raise ValueError(
            f"sensor bank has {spec.num_domains} channel(s) "
            f"{spec.domains} but the timeline carries "
            f"{dtl.num_domains} power rail(s) {dtl.domains}")


# ---------------------------------------------------------------------------
# Single-worker region pipeline: whole run in one jitted scan.
# ---------------------------------------------------------------------------


@jax.named_scope("alea/sensor")
def _blend_idle(chan, frac, idle_power, idle_ch: int):
    """§4.7 suspension overhead: blend toward idle proportionally to the
    per-period suspension fraction (frac = 0 → identity). On the scalar
    graph this is the pre-rail formula verbatim; on the channel matrix
    the idle power lands on the package rail (``idle_ch``, located by
    name via :func:`repro.core.sensors.idle_channel` — a suspended chip
    burns near-idle power in the package, not on HBM/ICI rails) and on
    the total channel so the scalar statistics see the same blend as
    before."""
    if chan.ndim == 1:
        return (1.0 - frac) * chan + frac * idle_power
    chan = (1.0 - frac) * chan
    chan = chan.at[idle_ch].add(frac * idle_power)
    return chan.at[-1].add(frac * idle_power)


@functools.lru_cache(maxsize=None)
def _region_run_fn(chunk_size: int, spec: SensorSpec, num_regions: int,
                   use_pallas: bool, grid_k: int):
    update = make_carry_update(num_regions, use_pallas=use_pallas)
    n_chan = num_channels(spec.num_domains)
    idle_ch = idle_channel(spec.domains)

    def run(ends, bounds, eint, powers, rids, m_true, grid, cell, root,
            period, jitter, t_end, frac, idle_power, n_chunks):
        arrs = (ends, bounds, eint, powers, rids, m_true, grid, cell)

        def body(k, carry):
            counts, psum, psumsq, n, prev = carry
            rid_mat, chan, valid, prev = _chunk_samples(
                arrs, spec, root, k, chunk_size, period, jitter, t_end,
                prev, grid_k)
            chan = _blend_idle(chan, frac, idle_power, idle_ch)
            counts, psum, psumsq = update(counts, psum, psumsq,
                                          rid_mat[0], chan, valid)
            return (counts, psum, psumsq, n + jnp.sum(valid), prev)

        stat_shape = (num_regions,) if n_chan == 1 \
            else (num_regions, n_chan)
        carry0 = (jnp.zeros(num_regions, jnp.int64),
                  jnp.zeros(stat_shape, jnp.float64),
                  jnp.zeros(stat_shape, jnp.float64),
                  jnp.zeros((), jnp.int64),
                  -jnp.ones((), jnp.float64))
        counts, psum, psumsq, n, _ = lax.fori_loop(0, n_chunks, body, carry0)
        return counts, psum, psumsq, n

    return jax.jit(run)


def region_pipeline_call(dtl: DeviceTimeline, spec: SensorSpec, *,
                         period: float, jitter: float = 200e-6,
                         seed: int = 0, chunk_size: int = DEFAULT_CHUNK,
                         overhead_per_sample: float = 0.0,
                         idle_power: float = DEFAULT_IDLE_POWER,
                         use_pallas: bool | None = None):
    """``(fn, args)``: the jitted fused run and the arguments
    :func:`run_region_pipeline` calls it with. Call it under
    ``enable_x64`` — or ``fn.lower(*args)`` to inspect the compiled
    program (e.g. that the Pallas reduction is in it)."""
    _check_sampling_args(spec, period, jitter)
    _check_spec_domains(spec, dtl)
    if dtl.num_workers != 1:
        raise ValueError(f"region pipeline is single-worker; got "
                         f"W={dtl.num_workers} (use run_combo_pipeline)")
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    frac = min(overhead_per_sample / period, 1.0) \
        if overhead_per_sample > 0.0 else 0.0
    k_chunks = num_chunks(dtl.t_end, period, chunk_size)
    fn = _region_run_fn(chunk_size, spec, dtl.num_regions,
                        bool(use_pallas), dtl.grid_k)
    args = (*dtl.arrays(), jax.random.PRNGKey(seed),
            jnp.float64(period), jnp.float64(jitter),
            jnp.float64(dtl.t_end), jnp.float64(frac),
            jnp.float64(idle_power), jnp.int32(k_chunks))
    return fn, args


def run_region_pipeline(dtl: DeviceTimeline, spec: SensorSpec, *,
                        period: float, jitter: float = 200e-6, seed: int = 0,
                        chunk_size: int = DEFAULT_CHUNK,
                        overhead_per_sample: float = 0.0,
                        idle_power: float = DEFAULT_IDLE_POWER,
                        use_pallas: bool | None = None) -> PipelineResult:
    """Fused single-worker profiling run, entirely on device.

    One jitted call scans every chunk through the fused step and folds
    into the (counts, Σpow, Σpow²) carry; only the final [R] statistics
    are transferred back. Statistically equivalent to
    ``sampler.iter_sample_chunks`` + ``StreamingAggregator`` (different
    but equally valid jitter process for the same seed);
    :func:`reference_region_pipeline` is the exact numpy mirror.
    """
    with enable_x64():
        fn, args = region_pipeline_call(
            dtl, spec, period=period, jitter=jitter, seed=seed,
            chunk_size=chunk_size, overhead_per_sample=overhead_per_sample,
            idle_power=idle_power, use_pallas=use_pallas)
        counts, psum, psumsq, n = fn(*args)
        n = int(n)
    if n == 0:
        raise ValueError("run too short for sampling period")
    return _result_from_channels(counts, psum, psumsq, n,
                                 dtl.t_end + n * overhead_per_sample,
                                 dtl.domains)


# ---------------------------------------------------------------------------
# Multi-worker combination pipeline: device table + host interner fallback.
# ---------------------------------------------------------------------------


def _lex_less(a, b):
    """Row-wise lexicographic a < b for [c, n_words] key matrices.

    Cascaded column compare (2 compares + 2 logic ops per word) — the
    word count is small (≤ ⌈W·bits/62⌉), so this beats a first-mismatch
    gather."""
    less = jnp.zeros(a.shape[0], bool)
    eq = jnp.ones(a.shape[0], bool)
    for col in range(a.shape[1]):
        ac, bc = a[:, col], b[:, col]
        less = less | (eq & (ac < bc))
        eq = eq & (ac == bc)
    return less


def _lex_search(table, n_rows, rows):
    """Vectorized lower-bound binary search of ``rows`` [c, W] in the
    lex-sorted ``table`` [cap, W] (first ``n_rows`` rows valid)."""
    cap = table.shape[0]
    c = rows.shape[0]
    lo = jnp.zeros(c, jnp.int32)
    hi = jnp.full(c, n_rows, jnp.int32)
    for _ in range(int(cap).bit_length()):
        active = lo < hi
        mid = (lo + hi) // 2
        less = active & _lex_less(table[mid], rows)
        lo = jnp.where(less, mid + 1, lo)
        hi = jnp.where(active & ~less, mid, hi)
    pos = jnp.clip(lo, 0, cap - 1)
    found = (lo < n_rows) & (table[pos] == rows).all(axis=1)
    return pos, found


def _pack_spec(num_regions: int, width: int) -> tuple[int, int, int]:
    """(bits per region id, ids per word, words per row) for packing
    worker-region rows into int64 key words: always fewer columns than
    the raw [W] row, one scalar word whenever ``W·bits ≤ 62`` (≤ 62 so a
    real key never collides with the int64-max table padding)."""
    bits = max((num_regions - 1).bit_length(), 1)
    per = max(62 // bits, 1)
    n_words = -(-width // per)
    return bits, per, n_words


def _pack_rows_np(mat: np.ndarray, pack: tuple[int, int, int]) -> np.ndarray:
    bits, per, n_words = pack
    w = mat.shape[1]
    out = np.zeros((len(mat), n_words), np.int64)
    for j in range(n_words):
        cols = mat[:, j * per:min((j + 1) * per, w)].astype(np.int64)
        shifts = np.arange(cols.shape[1], dtype=np.int64) * bits
        out[:, j] = (cols << shifts[None, :]).sum(axis=1)
    return out


def _pack_rows(rid_mat, pack: tuple[int, int, int]):
    """[W, c] device region-id matrix → [c, n_words] int64 key words."""
    bits, per, n_words = pack
    w = rid_mat.shape[0]
    words = []
    for j in range(n_words):
        cols = rid_mat[j * per:min((j + 1) * per, w)].astype(jnp.int64)
        shifts = jnp.arange(cols.shape[0], dtype=jnp.int64) * bits
        words.append((cols << shifts[:, None]).sum(axis=0))
    return jnp.stack(words, axis=1)


@functools.lru_cache(maxsize=None)
def _combo_step_fn(chunk_size: int, spec: SensorSpec, grid_k: int,
                   pack: tuple[int, int, int]):
    def step(carry, table, table_ids, n_rows, ends, bounds, eint, powers,
             rids, m_true, grid, cell, t_raw, t_end):
        counts, psum, psumsq, n, prev = carry
        prev_in = prev      # pre-chunk sensor state, for miss replay
        arrs = (ends, bounds, eint, powers, rids, m_true, grid, cell)
        rid_mat, chan, valid, prev = _samples_at(arrs, spec, t_raw, t_end,
                                                 prev, grid_k)
        cap = counts.shape[0]
        with jax.named_scope("alea/combo_key"):
            keys = _pack_rows(rid_mat, pack)
            if pack[2] == 1:
                # One int64 key per sample → scalar binary search.
                flat = keys[:, 0]
                pos = jnp.searchsorted(table[:, 0], flat, side="left")
                pos = jnp.minimum(pos, table.shape[0] - 1).astype(jnp.int32)
                found = (pos < n_rows) & (table[pos, 0] == flat)
            else:
                pos, found = _lex_search(table, n_rows, keys)
            # Any in-horizon row missing from the table aborts the
            # on-device fold for the WHOLE chunk — the host interns it and
            # re-folds, so no sample is ever half-counted.
            any_miss = jnp.any(valid & ~found)
            fold = valid & found & ~any_miss
            idx = jnp.where(fold, table_ids[pos], cap)
        with jax.named_scope("alea/combo_fold"):
            counts, psum, psumsq = _scatter_stats(counts, psum, psumsq,
                                                  idx, chan)
        carry = (counts, psum, psumsq, n + jnp.sum(fold), prev)
        return carry, any_miss, prev_in

    return jax.jit(step, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _chunk_recompute_fn(chunk_size: int, spec: SensorSpec, grid_k: int):
    """Miss-path sample recomputation: identical to the step's internal
    chunk at the same sample times, so replaying chunk k is exact — keeps
    sample arrays out of the steady-state step's outputs entirely."""
    def recompute(ends, bounds, eint, powers, rids, m_true, grid, cell,
                  t_raw, t_end, prev):
        arrs = (ends, bounds, eint, powers, rids, m_true, grid, cell)
        rid_mat, chan, valid, _ = _samples_at(arrs, spec, t_raw, t_end,
                                              prev, grid_k)
        return rid_mat, chan, valid
    return jax.jit(recompute)


def _scatter_stats(counts, psum, psumsq, idx, pows):
    """Scatter-add one chunk into the combination statistics; ``idx`` past
    the end drops the sample. ``pows`` is [c] (scalar substrate: the
    pre-rail graph) or the [C, c] channel matrix."""
    counts = counts.at[idx].add(jnp.ones((), counts.dtype), mode="drop")
    if psum.ndim == 1:
        psum = psum.at[idx].add(pows, mode="drop")
        psumsq = psumsq.at[idx].add(pows * pows, mode="drop")
    else:
        psum = psum.at[idx].add(pows.T, mode="drop")
        psumsq = psumsq.at[idx].add((pows * pows).T, mode="drop")
    return counts, psum, psumsq


@jax.named_scope("alea/combo_fold")
def _combo_fold(carry, idx, pows, valid):
    """Fixed-shape host-assisted fold for miss chunks: encoded combination
    ids (padded with the out-of-bounds cap index) scatter into the donated
    carry exactly like the on-device path would have."""
    counts, psum, psumsq, n, prev = carry
    counts, psum, psumsq = _scatter_stats(counts, psum, psumsq, idx, pows)
    return (counts, psum, psumsq, n + jnp.sum(valid), prev)


_combo_fold_jit = jax.jit(_combo_fold, donate_argnums=(0,))


def _build_table(interner: CombinationInterner, cap: int, width: int,
                 pack: tuple[int, int, int]):
    """Lex-sorted packed-key table [cap, n_words] int64 (int64-max
    padded) + sorted-position → interner id map."""
    mat = interner.combo_matrix()
    k = len(mat)
    ids = np.zeros(cap, np.int64)
    bits, per, n_words = pack
    table = np.full((cap, n_words), np.iinfo(np.int64).max, np.int64)
    if k:
        keys = _pack_rows_np(mat, pack)
        order = np.lexsort(keys.T[::-1])
        table[:k] = keys[order]
        ids[:k] = order
    with enable_x64():
        return jnp.asarray(table), jnp.asarray(ids), jnp.int32(k)


def _grow_carry(carry, pad: int):
    """The combination carry with ``pad`` more zero rows."""
    counts, psum, psumsq, n, prev = carry
    pad_stat = (pad, *psum.shape[1:])
    return (jnp.concatenate([counts, jnp.zeros(pad, counts.dtype)]),
            jnp.concatenate([psum, jnp.zeros(pad_stat, psum.dtype)]),
            jnp.concatenate([psumsq, jnp.zeros(pad_stat, psumsq.dtype)]),
            n, prev)


def _admit_or_fold(interner: CombinationInterner, rows: np.ndarray,
                   max_combinations: int, other_by_region: dict[int, int],
                   width: int) -> tuple[np.ndarray, int]:
    """Combination ids of a miss chunk's int64 ``rows`` in the bounded
    tier, and the samples folded into a tail row: new rows are interned
    while fewer than ``max_combinations`` identified rows exist; later
    arrivals fold into their region's ``other`` sentinel row, so the
    table/carry stop growing. Folded keys stay out of the device table —
    their traffic keeps re-missing — but each miss lands here and folds
    exactly once per sample, so nothing is lost."""
    uniq, inverse = np.unique(rows, axis=0, return_inverse=True)
    uids = np.empty(len(uniq), np.int64)
    folds = 0
    for i in range(len(uniq)):
        key = tuple(int(v) for v in uniq[i])
        cid = interner.find_row(uniq[i])
        if cid is None:
            resident = len(interner) - len(other_by_region)
            if resident < max_combinations:
                cid = interner.intern(key)
            else:
                region = key[0]
                cid = other_by_region.get(region)
                if cid is None:
                    cid = interner.intern(other_row(region, width))
                    other_by_region[region] = cid
                folds += int(np.sum(inverse == i))
        uids[i] = cid
    return uids[inverse.reshape(-1)], folds


def run_combo_pipeline(dtl: DeviceTimeline, spec: SensorSpec, *,
                       period: float, jitter: float = 200e-6, seed: int = 0,
                       chunk_size: int = DEFAULT_CHUNK,
                       max_combinations: int | None = None,
                       stats: dict | None = None
                       ) -> tuple[StreamingCombinationAggregator, int]:
    """Fused multi-worker (§4.4) combination attribution.

    The chunk's sample times come from the host's CPU (:func:`_ref_times`:
    8 bytes a sample uploaded, exact where the device emulates float64).
    The jitted chunk step looks every sample's worker-region row up in
    the device-side lex-sorted combination table and scatters into the
    donated carry; only a scalar miss flag is read back per chunk. A chunk
    that surfaces a new combination falls back to the host: the chunk is
    computed again on the device and read back, its rows are interned
    (the combination id space is dynamic and first-appearance-ordered —
    host-authoritative), and the rebuilt table is re-uploaded.

    Whether the device-resident steady state arrives depends on the
    traffic. Where the workers' phases repeat (lockstep or fixed-offset
    replicas), the combination set stops growing and misses stop after
    O(distinct combos / chunk) chunks. On SPMD traffic — every worker
    running the same steps with launch skew and op-time jitter, and a
    barrier at every step — the skew keeps producing new combinations:
    in the ``combo-call-rapl-w16.spmd-barrier`` benchmark cell (16
    workers, 65,536-sample chunks) each chunk brings thousands of new
    rows, ``miss_chunks == chunks``, and every chunk is computed twice
    and interned on the host.

    ``max_combinations`` bounds the attribution state (heavy-hitters
    tier, see :mod:`repro.core.sketch`): the miss path *admits* new
    combinations while fewer than ``max_combinations`` identified rows
    exist, and *folds* later arrivals into their region's ``other``
    sentinel row — the device table, carry and final aggregator stay
    O(max_combinations + regions) instead of growing with the distinct
    count. Per-region sample counts stay exact; tail identity coarsens.
    Folded (non-admitted) combinations never enter the device table, so
    chunks carrying tail traffic keep taking the host fold path —
    bounded memory trades away the tail's zero-transfer steady state,
    never correctness. With ``max_combinations >= distinct`` nothing
    folds and the result is bit-exact to the unbounded run.

    Returns ``(aggregator, n_samples)`` — the aggregator is a regular
    :class:`StreamingCombinationAggregator`, so merge/exchange/estimates
    compose exactly as with the host path. ``stats``, if given, records
    ``chunks``, ``miss_chunks`` (host-fallback count — the steady state
    holds where ``miss_chunks ≪ chunks``), ``distinct_combinations`` (the
    interner's rows at the end), ``rows_interned`` (sample rows read back
    and interned on the host) and ``table_rebuilds`` (device table
    uploads after the first, one a miss chunk) plus, in bounded mode,
    ``tail_folds``.
    """
    _check_sampling_args(spec, period, jitter)
    _check_spec_domains(spec, dtl)
    W = dtl.num_workers
    if max_combinations is not None:
        if max_combinations < 1:
            raise ValueError(f"max_combinations must be >= 1; "
                             f"got {max_combinations}")
        if W < 2:
            raise SketchConfigError(
                "bounded combination attribution needs >= 2 workers (the "
                "region axis plus at least one folded axis); at W=1 use "
                "the region pipeline")
    miss_chunks = 0
    tail_folds = 0
    rows_interned = 0
    table_rebuilds = 0
    other_by_region: dict[int, int] = {}
    n_chan = num_channels(dtl.num_domains)
    pack = _pack_spec(dtl.num_regions, W)
    interner = CombinationInterner()
    with enable_x64():
        step = _combo_step_fn(chunk_size, spec, dtl.grid_k, pack)
        cap = _TABLE_MIN
        stat_shape = (cap,) if n_chan == 1 else (cap, n_chan)
        table, table_ids, n_rows = _build_table(interner, cap, W, pack)
        carry = (jnp.zeros(cap, jnp.int64),
                 jnp.zeros(stat_shape, jnp.float64),
                 jnp.zeros(stat_shape, jnp.float64),
                 jnp.zeros((), jnp.int64),
                 -jnp.ones((), jnp.float64))
        t_end_j = jnp.float64(dtl.t_end)
        k_chunks = num_chunks(dtl.t_end, period, chunk_size)
        for k in range(k_chunks):
            t_raw = jnp.asarray(_ref_times(seed, k, period, jitter,
                                           chunk_size))
            carry, miss, prev_in = step(
                carry, table, table_ids, n_rows, *dtl.arrays(), t_raw,
                t_end_j)
            if not bool(miss):
                continue
            # Miss path: replay this one chunk (counter-based times make
            # the replay exact), intern the new rows, rebuild, re-fold.
            miss_chunks += 1
            with jax.profiler.TraceAnnotation("alea/miss"):
                with jax.profiler.TraceAnnotation("alea/miss/recompute"):
                    rid_dev, total_dev, valid_dev = _chunk_recompute_fn(
                        chunk_size, spec, dtl.grid_k)(
                            *dtl.arrays(), t_raw, t_end_j, prev_in)
                    valid = np.asarray(valid_dev)
                    rows = np.asarray(rid_dev).T[valid].astype(np.int64)
                with jax.profiler.TraceAnnotation("alea/miss/intern"):
                    rows_interned += len(rows)
                    if max_combinations is None:
                        cids = interner.encode(rows)
                    else:
                        cids, folds = _admit_or_fold(
                            interner, rows, max_combinations,
                            other_by_region, W)
                        tail_folds += folds
                with jax.profiler.TraceAnnotation("alea/miss/table"):
                    if len(interner) > cap:
                        new_cap = 1 << (len(interner) - 1).bit_length()
                        carry = _grow_carry(carry, new_cap - cap)
                        cap = new_cap
                    table, table_ids, n_rows = _build_table(interner, cap,
                                                            W, pack)
                    table_rebuilds += 1
                    idx = np.full(chunk_size, cap, np.int64)
                    idx[valid] = cids
                    carry = _combo_fold_jit(carry, jnp.asarray(idx),
                                            total_dev, valid_dev)
        counts, psum, psumsq, n, _ = carry
        k_combos = len(interner)
        n = int(n)
        counts = np.asarray(counts, np.int64)[:k_combos]
        psum = np.asarray(psum, np.float64)[:k_combos]
        psumsq = np.asarray(psumsq, np.float64)[:k_combos]
    if stats is not None:
        stats["chunks"] = k_chunks
        stats["miss_chunks"] = miss_chunks
        stats["distinct_combinations"] = k_combos
        stats["rows_interned"] = rows_interned
        stats["table_rebuilds"] = table_rebuilds
        if max_combinations is not None:
            stats["tail_folds"] = tail_folds
    if n == 0:
        raise ValueError("run too short for sampling period")
    agg = StreamingCombinationAggregator.from_table(
        interner.combo_matrix(), counts, psum, psumsq,
        domains=dtl.domains, k=max_combinations)
    if max_combinations is not None:
        # from_table re-counts nothing; carry the pipeline's fold
        # provenance so tail_info() discloses what happened on device.
        agg.tail_folds += tail_folds
    return agg, n


# ---------------------------------------------------------------------------
# Numpy reference oracle (same sample clock, float64 host math).
# ---------------------------------------------------------------------------


def _ref_times(seed: int, k: int, period: float, jitter: float,
               chunk_size: int) -> np.ndarray:
    """Chunk ``k``'s sample times, :func:`chunk_sample_times` run on the
    host's CPU: IEEE float64, where the contract's nanosecond rounding is
    exact. A device that emulates float64 (the TPU) can round a time to
    the neighbouring nanosecond, so the combination pipeline takes its
    times from here too."""
    with enable_x64(), jax.default_device(jax.local_devices(
            backend="cpu")[0]):
        t = chunk_sample_times(jax.random.PRNGKey(seed), jnp.int32(k),
                               jnp.float64(period), jnp.float64(jitter),
                               chunk_size=chunk_size)
        return np.asarray(t, np.float64)


def _ref_reader(spec: SensorSpec, tl: Timeline):
    """Per-run chunk reader ``(t, valid, prev) -> (rails [n, D], new_prev)``.

    Sensors/precomputations are built once per run (not per chunk); the
    RAPL prev-sample state is carried by the caller because it crosses
    chunk boundaries. The instant/INA231 branches reuse the real trace
    sensors' ``read_rails`` (stateless semantics) so the oracle can't
    drift from the instrument model. For scalar (D=1) timelines the
    single rail column is bit-identical to the old scalar reader.
    """
    if spec.kind == "instant":
        from repro.core.sensors import InstantTraceSensor
        sens = InstantTraceSensor(tl)
        return lambda t, valid, prev: (sens.read_rails(t), prev)
    if spec.kind == "rapl":
        base = _TraceSensorBase(tl)
        up = spec.update_period

        def read(t, valid, prev):
            tq = np.floor(t / up + 1e-6) * up
            prev_vec = np.concatenate([[prev], tq[:-1]])
            prev_vec = np.where(prev_vec < 0.0, np.maximum(tq - up, 0.0),
                                prev_vec)
            dt = np.maximum(tq - prev_vec, up)
            p = (base._energy_rails_at(tq)
                 - base._energy_rails_at(prev_vec)) / dt[:, None]
            new_prev = float(tq[valid][-1]) if valid.any() else prev
            return p, new_prev
        return read
    if spec.kind == "ina231":
        from repro.core.sensors import Ina231TraceSensor
        sens = Ina231TraceSensor(tl, window=spec.window)
        return lambda t, valid, prev: (sens.read_rails(t), prev)
    raise ValueError(f"unknown trace sensor kind: {spec.kind!r}")


def _ref_channels(rails: np.ndarray) -> np.ndarray:
    """[n, D] rails → [n, C] channels (total appended when D > 1)."""
    if rails.shape[1] == 1:
        return rails
    return np.concatenate([rails, rails.sum(axis=1, keepdims=True)], axis=1)


def reference_region_pipeline(tl: Timeline, spec: SensorSpec, *,
                              period: float, jitter: float = 200e-6,
                              seed: int = 0,
                              chunk_size: int = DEFAULT_CHUNK,
                              overhead_per_sample: float = 0.0,
                              idle_power: float = DEFAULT_IDLE_POWER) -> PipelineResult:
    """Numpy mirror of :func:`run_region_pipeline` (the oracle).

    Same counter-based times (shared :func:`chunk_sample_times`), host
    ``searchsorted`` lookups, float64 sensor math, ``np.bincount``
    reduction. Counts must match the fused path bit-exactly; sums agree
    to float64 elementwise-rounding differences.
    """
    _check_sampling_args(spec, period, jitter)
    if spec.num_domains != tl.num_domains:
        raise ValueError(
            f"sensor bank has {spec.num_domains} channel(s) but the "
            f"timeline carries {tl.num_domains} power rail(s)")
    R = len(tl.names)
    C = num_channels(tl.num_domains)
    idle_ch = idle_channel(tl.domain_names)
    reader = _ref_reader(spec, tl)
    frac = min(overhead_per_sample / period, 1.0) \
        if overhead_per_sample > 0.0 else 0.0
    counts = np.zeros(R, np.int64)
    psum = np.zeros((R, C), np.float64)
    psumsq = np.zeros((R, C), np.float64)
    prev = -1.0
    t_end = tl.t_exec
    n = 0
    for k in range(num_chunks(t_end, period, chunk_size)):
        t_raw = _ref_times(seed, k, period, jitter, chunk_size)
        valid = t_raw < t_end
        t = np.minimum(t_raw, t_end)
        rids = tl.region_at(t)
        rails, prev = reader(t, valid, prev)
        chan = (1.0 - frac) * _ref_channels(rails)
        chan[:, idle_ch] += frac * idle_power
        if C > 1:
            chan[:, -1] += frac * idle_power
        rv, pv = rids[valid], chan[valid]
        counts += np.bincount(rv, minlength=R).astype(np.int64)
        for j in range(C):
            psum[:, j] += np.bincount(rv, weights=pv[:, j], minlength=R)
            psumsq[:, j] += np.bincount(rv, weights=pv[:, j] * pv[:, j],
                                        minlength=R)
        n += int(valid.sum())
    if n == 0:
        raise ValueError("run too short for sampling period")
    return _result_from_channels(counts, psum, psumsq, n,
                                 t_end + n * overhead_per_sample,
                                 tl.domain_names)


def reference_combo_pipeline(timelines: list[Timeline], spec_fn, *,
                             period: float, jitter: float = 200e-6,
                             seed: int = 0,
                             chunk_size: int = DEFAULT_CHUNK
                             ) -> tuple[StreamingCombinationAggregator, int]:
    """Numpy mirror of :func:`run_combo_pipeline`.

    ``spec_fn`` maps a timeline to its :class:`SensorSpec` (matching the
    device path's one-spec-for-all, pass ``lambda tl: spec``). Chunks are
    interned through a host :class:`CombinationInterner` exactly as the
    device path's miss fallback does, so combination ids line up 1:1.
    """
    specs = [spec_fn(tl) for tl in timelines]
    for s, tl in zip(specs, timelines):
        _check_sampling_args(s, period, jitter)
        if s.num_domains != tl.num_domains:
            raise ValueError("sensor bank / timeline rail count mismatch")
    domains = timelines[0].domain_names
    if any(tl.domain_names != domains for tl in timelines):
        raise ValueError("workers must share a power-rail domain axis")
    readers = [_ref_reader(s, tl) for s, tl in zip(specs, timelines)]
    t_end = min(tl.t_exec for tl in timelines)
    agg = StreamingCombinationAggregator(domains=domains)
    prev = -1.0
    n = 0
    for k in range(num_chunks(t_end, period, chunk_size)):
        t_raw = _ref_times(seed, k, period, jitter, chunk_size)
        valid = t_raw < t_end
        t = np.minimum(t_raw, t_end)
        rid_mat = np.stack([tl.region_at(t) for tl in timelines], axis=1)
        rails = np.zeros((len(t), len(domains)), np.float64)
        new_prev = prev
        for reader in readers:
            p, new_prev = reader(t, valid, prev)
            rails += p
        prev = new_prev
        pv = rails[valid]
        agg.update(rid_mat[valid].astype(np.int64),
                   pv[:, 0] if len(domains) == 1 else pv)
        n += int(valid.sum())
    if n == 0:
        raise ValueError("run too short for sampling period")
    return agg, n
