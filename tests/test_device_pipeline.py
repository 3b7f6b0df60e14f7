"""Fused device pipeline ≡ numpy reference (same counter-based sample
clock): bit-exact counts, float64-tolerance sums, donated carries, and the
profiler/benchmark wiring."""

import math

import numpy as np
import pytest

from repro.core import device_pipeline as dp
from repro.core.profiler import EnergyProfiler
from repro.core.sensors import (Ina231TraceSensor, InstantTraceSensor,
                                RaplTraceSensor)
from repro.core.timeline import RegionCost, Timeline, ground_truth, synthesize

_SENSORS = {
    "instant": InstantTraceSensor,
    "rapl": RaplTraceSensor,
    "ina231": Ina231TraceSensor,
}


def _timelines(w, steps=60, base_seed=0):
    costs = [RegionCost("mem", flops=1e10, hbm_bytes=5e10, invocations=4),
             RegionCost("alu", flops=6e11, hbm_bytes=2e9, invocations=4),
             RegionCost("opt", flops=2e10, hbm_bytes=4e10, invocations=1)]
    return [synthesize(costs, steps=steps, seed=base_seed + s)
            for s in range(w)]


def _assert_stats_close(got, want, rtol=1e-9):
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_allclose(got[1], want[1], rtol=rtol)
    np.testing.assert_allclose(got[2], want[2], rtol=rtol)


# ---------------------------------------------------------------------------
# Region (single-worker) pipeline ≡ reference.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sensor", ["instant", "rapl", "ina231"])
def test_region_pipeline_matches_reference(sensor):
    (tl,) = _timelines(1)
    spec = _SENSORS[sensor].make_spec()
    res = dp.run_region_pipeline(tl.to_device(), spec, period=10e-3,
                                 jitter=200e-6, seed=3, chunk_size=1024)
    ref = dp.reference_region_pipeline(tl, spec, period=10e-3,
                                       jitter=200e-6, seed=3,
                                       chunk_size=1024)
    assert res.n == ref.n
    assert res.t_exec == ref.t_exec
    _assert_stats_close((res.counts, res.psum, res.psumsq),
                        (ref.counts, ref.psum, ref.psumsq))


def test_region_pipeline_overhead_blending_matches_reference():
    (tl,) = _timelines(1)
    spec = InstantTraceSensor.make_spec()
    kw = dict(period=5e-3, jitter=100e-6, seed=9, chunk_size=512,
              overhead_per_sample=1e-3, idle_power=55.0)
    res = dp.run_region_pipeline(tl.to_device(), spec, **kw)
    ref = dp.reference_region_pipeline(tl, spec, **kw)
    assert res.n == ref.n
    assert res.t_exec == pytest.approx(tl.t_exec + res.n * 1e-3)
    _assert_stats_close((res.counts, res.psum, res.psumsq),
                        (ref.counts, ref.psum, ref.psumsq))


def test_region_pipeline_deterministic_and_chunk_grid_keyed():
    """Statistics are a pure function of (seed, chunk grid): identical
    across runs at the same chunk size, and still oracle-exact at any
    other chunk size (each grid draws its own — equally valid — jitter
    sequence, like the host streaming path does vs the one-shot path)."""
    (tl,) = _timelines(1)
    spec = RaplTraceSensor.make_spec()
    a = dp.run_region_pipeline(tl.to_device(), spec, period=10e-3, seed=1,
                               chunk_size=768)
    b = dp.run_region_pipeline(tl.to_device(), spec, period=10e-3, seed=1,
                               chunk_size=768)
    _assert_stats_close((a.counts, a.psum, a.psumsq),
                        (b.counts, b.psum, b.psumsq), rtol=0.0)
    c = dp.run_region_pipeline(tl.to_device(), spec, period=10e-3, seed=1,
                               chunk_size=2048)
    ref = dp.reference_region_pipeline(tl, spec, period=10e-3, seed=1,
                                       chunk_size=2048)
    np.testing.assert_array_equal(c.counts, ref.counts)
    # Different grids sample the same process: totals agree closely.
    assert c.n == pytest.approx(a.n, rel=0.02)


def test_region_pipeline_validates_args():
    (tl,) = _timelines(1)
    with pytest.raises(ValueError):   # period below sensor minimum
        dp.run_region_pipeline(tl.to_device(),
                               Ina231TraceSensor.make_spec(window=280e-6),
                               period=100e-6)
    with pytest.raises(ValueError):   # jitter > period: non-monotone clock
        dp.run_region_pipeline(tl.to_device(),
                               InstantTraceSensor.make_spec(),
                               period=1e-3, jitter=5e-3)
    with pytest.raises(ValueError):   # multi-worker needs combo pipeline
        dp.run_region_pipeline(
            dp.DeviceTimeline.from_timelines(_timelines(2)),
            InstantTraceSensor.make_spec(), period=1e-3)


# ---------------------------------------------------------------------------
# Combination (multi-worker) pipeline ≡ reference.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("w", [1, 4])
@pytest.mark.parametrize("sensor", ["instant", "rapl", "ina231"])
def test_combo_pipeline_matches_reference(sensor, w):
    tls = _timelines(w)
    spec = _SENSORS[sensor].make_spec()
    dtl = dp.DeviceTimeline.from_timelines(tls)
    agg, n = dp.run_combo_pipeline(dtl, spec, period=10e-3, jitter=200e-6,
                                   seed=7, chunk_size=512)
    ragg, rn = dp.reference_combo_pipeline(tls, lambda tl: spec,
                                           period=10e-3, jitter=200e-6,
                                           seed=7, chunk_size=512)
    assert n == rn
    # Device misses intern through the same chunk-order first-appearance
    # process as the reference, so ids (not just sets) line up.
    assert agg.interner.combos == ragg.interner.combos
    _assert_stats_close((agg.agg.counts, agg.agg.psum, agg.agg.psumsq),
                        (ragg.agg.counts, ragg.agg.psum, ragg.agg.psumsq))


def test_combo_pipeline_multiword_keys_match_reference():
    """W·bits > 62 forces the multi-word packed-key path
    (_lex_less/_lex_search): a wide region space (R=300 → 9 bits) across
    W=8 workers packs to 2 int64 words per row."""
    rng = np.random.default_rng(23)
    R, m = 300, 50
    names = tuple(f"bb_{i}" for i in range(R))
    base = Timeline(rng.integers(0, R, m).astype(np.int32),
                    rng.uniform(5e-3, 15e-3, m),
                    50.0 + 150.0 * rng.random(m), names).tile(8)
    tls = []
    for w in range(8):
        # Phase-shifted copies of one tiled structure: combination pairs
        # repeat after the first tile, so later chunks must fold through
        # the device-side multi-word table search (not the miss path).
        tls.append(Timeline(
            np.concatenate([[base.region_ids[0]], base.region_ids]),
            np.concatenate([[w * 2e-4 + 1e-9], base.durations]),
            np.concatenate([[base.powers[0]], base.powers]), names))
    assert dp._pack_spec(R, 8)[2] >= 2
    spec = RaplTraceSensor.make_spec()
    dtl = dp.DeviceTimeline.from_timelines(tls)
    stats = {}
    agg, n = dp.run_combo_pipeline(dtl, spec, period=2e-3, jitter=100e-6,
                                   seed=5, chunk_size=256, stats=stats)
    assert stats["miss_chunks"] < stats["chunks"]   # device folds happened
    ragg, rn = dp.reference_combo_pipeline(tls, lambda tl: spec,
                                           period=2e-3, jitter=100e-6,
                                           seed=5, chunk_size=256)
    assert n == rn
    assert agg.interner.combos == ragg.interner.combos
    _assert_stats_close((agg.agg.counts, agg.agg.psum, agg.agg.psumsq),
                        (ragg.agg.counts, ragg.agg.psum, ragg.agg.psumsq))


def test_combo_pipeline_steady_state_stops_transferring():
    """Once the combination table is complete, chunks fold on device:
    misses stop long before the run does (the zero-per-chunk-transfer
    steady state of the acceptance criteria)."""
    tls = _timelines(2, steps=120)
    dtl = dp.DeviceTimeline.from_timelines(tls)
    stats = {}
    agg, n = dp.run_combo_pipeline(dtl, InstantTraceSensor.make_spec(),
                                   period=5e-3, seed=0, chunk_size=256,
                                   stats=stats)
    assert n > 0
    assert stats["chunks"] >= 10
    # Misses are bounded by distinct-combination appearances, not run
    # length: a strict majority of chunks must fold with no fallback.
    assert stats["miss_chunks"] < stats["chunks"] / 2
    assert stats["miss_chunks"] <= len(agg.interner)


def test_chunk_step_carry_is_donated():
    """The donated carry contract: after a step, the previous carry's
    buffers are consumed (no second live copy of the accumulators)."""
    import jax.numpy as jnp
    from jax import enable_x64
    tls = _timelines(2, steps=20)
    dtl = dp.DeviceTimeline.from_timelines(tls)
    spec = InstantTraceSensor.make_spec()
    pack = dp._pack_spec(dtl.num_regions, 2)
    with enable_x64():
        step = dp._combo_step_fn(256, spec, dtl.grid_k, pack)
        cap = dp._TABLE_MIN
        table, tids, n_rows = dp._build_table(dp.CombinationInterner(),
                                              cap, 2, pack)
        carry = (jnp.zeros(cap, jnp.int64), jnp.zeros(cap, jnp.float64),
                 jnp.zeros(cap, jnp.float64), jnp.zeros((), jnp.int64),
                 -jnp.ones((), jnp.float64))
        new_carry, miss, *_ = step(carry, table, tids, n_rows,
                                   *dtl.arrays(),
                                   jnp.asarray(dp._ref_times(
                                       0, 0, 1e-2, 2e-4, 256)),
                                   jnp.float64(dtl.t_end))
    assert all(buf.is_deleted() for buf in carry)
    assert not any(buf.is_deleted() for buf in new_carry[:3])


def _spmd_barrier_timelines(w, steps=150, seed=0, skew=2e-4):
    """SPMD workers with a barrier at every step: every worker runs the
    same ops in the same order (its own op-time jitter), starts each step
    a half-normal skew after the slowest worker's previous step ended, and
    waits in the ``barrier`` region (id 0) until then. Worker 0 stops
    after its last op, so the workers are ragged."""
    rng = np.random.default_rng(seed)
    R, n_ops = 24, 30
    names = ("barrier",) + tuple(f"op{i}" for i in range(1, R))
    ids = rng.integers(1, R, n_ops)
    rec = rng.uniform(1e-4, 1e-3, n_ops)
    durs = rec * np.clip(1 + 0.02 * rng.standard_normal((w, steps, n_ops)),
                         0.9, 1.1)
    off = skew * np.abs(rng.standard_normal((w, steps)))
    busy = off + durs.sum(axis=2)
    lead = busy.max(axis=0) - busy
    waits = np.concatenate([off[:, :1], lead[:, :-1] + off[:, 1:],
                            lead[:, -1:]], axis=1)
    tls = []
    for k in range(w):
        rid, dur = [], []
        for s in range(steps):
            rid += [0, *ids]
            dur += [waits[k, s], *durs[k, s]]
        if k:
            rid.append(0)
            dur.append(waits[k, -1] + 1e-3)
        rid, dur = np.array(rid, np.int32), np.array(dur)
        tls.append(Timeline(rid, dur, 40.0 + 20.0 * rid + rng.random(len(rid)),
                            names))
    return tls


def test_combo_pipeline_spmd_barrier_matches_reference():
    """Ragged SPMD-barrier workers: counts equal the reference's, ids line
    up, and the combinations the skew keeps making miss every chunk."""
    tls = _spmd_barrier_timelines(4)
    assert len({len(tl.region_ids) for tl in tls}) == 2
    spec = RaplTraceSensor.make_spec()
    dtl = dp.DeviceTimeline.from_timelines(tls)
    stats = {}
    agg, n = dp.run_combo_pipeline(dtl, spec, period=1e-3, jitter=2e-4,
                                   seed=11, chunk_size=128, stats=stats)
    ragg, rn = dp.reference_combo_pipeline(tls, lambda tl: spec,
                                           period=1e-3, jitter=2e-4,
                                           seed=11, chunk_size=128)
    assert n == rn
    assert agg.interner.combos == ragg.interner.combos
    _assert_stats_close((agg.agg.counts, agg.agg.psum, agg.agg.psumsq),
                        (ragg.agg.counts, ragg.agg.psum, ragg.agg.psumsq))


@pytest.mark.parametrize("seed,k", [(0, 0), (11, 3), (2022890337, 1)])
def test_host_clock_is_the_float64_contract(seed, k):
    """The combination pipeline's sample times (``_ref_times``, run on the
    host's CPU) are the contract's formula in IEEE float64 bit for bit:
    numpy's arithmetic on the same threefry draws."""
    import jax
    from jax import enable_x64
    c, period, jitter = 65536, 1e-3, 2e-4
    with enable_x64(), jax.default_device(jax.local_devices(
            backend="cpu")[0]):
        root = jax.random.PRNGKey(seed)
        u0 = float(jax.random.uniform(jax.random.fold_in(root, 0), (),
                                      "float64", 0.0, period))
        u = np.asarray(jax.random.uniform(jax.random.fold_in(root, k + 1),
                                          (c,), "float64", 0.0, jitter))
    i = np.arange(k * c, (k + 1) * c, dtype=np.int64)
    t = u0 + i.astype(np.float64) * period + u
    want = np.floor(t * 1e9 + 0.5) * 1e-9
    got = dp._ref_times(seed, k, period, jitter, c)
    assert got.dtype == np.float64
    np.testing.assert_array_equal(got.view(np.int64), want.view(np.int64))


def test_combo_pipeline_takes_its_times_from_the_host_clock(monkeypatch):
    """Every chunk of a combination run, and its replay on a miss, is
    attributed at the host clock's times for that chunk."""
    real, drawn = dp._ref_times, []

    def spy(seed, k, period, jitter, chunk_size):
        drawn.append((seed, k, period, jitter, chunk_size))
        return real(seed, k, period, jitter, chunk_size)

    monkeypatch.setattr(dp, "_ref_times", spy)
    tls = _spmd_barrier_timelines(4, steps=60)
    dtl = dp.DeviceTimeline.from_timelines(tls)
    stats = {}
    dp.run_combo_pipeline(dtl, RaplTraceSensor.make_spec(), period=1e-3,
                          jitter=2e-4, seed=5, chunk_size=128, stats=stats)
    assert stats["miss_chunks"] >= 1
    assert drawn == [(5, k, 1e-3, 2e-4, 128)
                     for k in range(stats["chunks"])]


def test_combo_pipeline_reports_the_miss_path():
    """``stats`` names the host's share of the work: every chunk of the
    SPMD-barrier run brings new rows, so every chunk misses, every miss
    reads its valid rows back and rebuilds the table once, and the
    distinct count is the interner's."""
    tls = _spmd_barrier_timelines(4, seed=3)
    dtl = dp.DeviceTimeline.from_timelines(tls)
    stats = {}
    agg, n = dp.run_combo_pipeline(dtl, RaplTraceSensor.make_spec(),
                                   period=1e-3, jitter=2e-4, seed=2,
                                   chunk_size=128, stats=stats)
    # The chunks that hold a combination no earlier chunk held, on the
    # host: every one of them, and no other, misses.
    t_end = min(tl.t_exec for tl in tls)
    seen, fresh = set(), 0
    for k in range(stats["chunks"]):
        t = dp._ref_times(2, k, 1e-3, 2e-4, 128)
        rows = np.stack([tl.region_at(np.minimum(t, t_end)) for tl in tls],
                        axis=1)[t < t_end]
        got = set(map(tuple, rows.tolist()))
        fresh += bool(got - seen)
        seen |= got
    assert stats["chunks"] >= 4
    assert stats["miss_chunks"] == fresh == stats["chunks"]
    assert stats["distinct_combinations"] == len(agg.interner) == len(seen)
    assert stats["rows_interned"] == n
    assert stats["table_rebuilds"] == stats["miss_chunks"]
    # Phase-repeating workers reach the steady state: later chunks fold on
    # the device, nothing is read back for them.
    stats = {}
    agg, n = dp.run_combo_pipeline(
        dp.DeviceTimeline.from_timelines(_timelines(2, steps=120)),
        InstantTraceSensor.make_spec(), period=5e-3, seed=0,
        chunk_size=256, stats=stats)
    assert stats["miss_chunks"] < stats["chunks"]
    assert stats["rows_interned"] < n
    assert stats["table_rebuilds"] == stats["miss_chunks"]
    assert stats["distinct_combinations"] == len(agg.interner)


def test_bounded_combo_pipeline_reports_tail_folds():
    """In the bounded tier the miss path folds what the table cannot admit
    into tail rows: every sample is still counted once, and each miss
    chunk rebuilds the table once."""
    tls = _spmd_barrier_timelines(4, seed=5)
    dtl = dp.DeviceTimeline.from_timelines(tls)
    stats = {}
    agg, n = dp.run_combo_pipeline(dtl, RaplTraceSensor.make_spec(),
                                   period=1e-3, jitter=2e-4, seed=4,
                                   chunk_size=128, max_combinations=8,
                                   stats=stats)
    assert 0 < stats["tail_folds"] < n
    assert stats["miss_chunks"] == stats["chunks"]
    assert stats["table_rebuilds"] == stats["miss_chunks"]
    assert stats["rows_interned"] == n == int(np.sum(agg.agg.counts))
    assert stats["distinct_combinations"] == len(agg.interner)


# ---------------------------------------------------------------------------
# Stage names: each stage of the chunk step is a named scope, so the
# compiled program's op_name metadata (and a device trace) names it.
# ---------------------------------------------------------------------------

STAGES = ("clock", "lookup", "sensor", "reduce")


@pytest.mark.parametrize("domains", [False, True], ids=["D1", "D3"])
def test_region_pipeline_hlo_names_every_stage(domains):
    import re

    from jax import enable_x64
    costs = [RegionCost("mem", flops=1e10, hbm_bytes=5e10, invocations=4),
             RegionCost("alu", flops=6e11, hbm_bytes=2e9, invocations=4)]
    dtl = synthesize(costs, steps=8, seed=0, domains=domains).to_device()
    spec = RaplTraceSensor.make_spec(domains=dtl.domains)
    with enable_x64():
        fn, args = dp.region_pipeline_call(dtl, spec, period=10e-3,
                                           chunk_size=1024)
        hlo = fn.lower(*args).as_text(dialect="hlo", debug_info=True)
    op_names = re.findall(r'op_name="([^"]*)"', hlo)
    assert {m for n in op_names
            for m in re.findall(r"alea/(\w+)", n)} == set(STAGES)
    # Scopes nest, and the innermost names the operation: the lookups of
    # the RAPL refresh times sit inside the sensor stage.
    assert any(re.search(r"alea/sensor/.*alea/lookup", n) for n in op_names)


def test_combo_step_hlo_names_key_and_fold():
    """The combination step packs and searches its keys under
    ``alea/combo_key`` and scatters under ``alea/combo_fold``; the miss
    path's fold scatters under ``alea/combo_fold`` too."""
    import re

    import jax.numpy as jnp
    from jax import enable_x64
    tls = _spmd_barrier_timelines(16, steps=4)
    dtl = dp.DeviceTimeline.from_timelines(tls)
    spec = RaplTraceSensor.make_spec()
    pack = dp._pack_spec(dtl.num_regions, 16)
    cap = dp._TABLE_MIN
    with enable_x64():
        step = dp._combo_step_fn(256, spec, dtl.grid_k, pack)
        table, tids, n_rows = dp._build_table(dp.CombinationInterner(),
                                              cap, 16, pack)
        carry = (jnp.zeros(cap, jnp.int64), jnp.zeros(cap, jnp.float64),
                 jnp.zeros(cap, jnp.float64), jnp.zeros((), jnp.int64),
                 -jnp.ones((), jnp.float64))
        hlo = step.lower(carry, table, tids, n_rows, *dtl.arrays(),
                         jnp.asarray(dp._ref_times(0, 0, 1e-3, 2e-4, 256)),
                         jnp.float64(dtl.t_end)).as_text(
                             dialect="hlo", debug_info=True)
        fold = dp._combo_fold_jit.lower(
            carry, jnp.zeros(256, jnp.int64), jnp.zeros(256, jnp.float64),
            jnp.ones(256, bool)).as_text(dialect="hlo", debug_info=True)
    op_names = re.findall(r'op_name="([^"]*)"', hlo)
    scopes = {m for n in op_names for m in re.findall(r"alea/(\w+)", n)}
    assert {"combo_key", "combo_fold", "lookup", "sensor"} <= scopes
    assert any("scatter" in line and "alea/combo_fold" in line
               for line in hlo.splitlines())
    assert re.search(r'op_name="[^"]*alea/combo_fold', fold)


# ---------------------------------------------------------------------------
# DeviceTimeline substrate.
# ---------------------------------------------------------------------------

def _heavy_tailed_timeline():
    """One long interval + 4,000 micro-intervals: the micro-intervals
    crowd ~160 to a grid cell."""
    rng = np.random.default_rng(31)
    m = 4000
    return Timeline(rng.integers(0, 4, m + 1).astype(np.int32),
                    np.concatenate([[5.0], rng.uniform(1e-6, 3e-6, m)]),
                    50.0 + 100.0 * rng.random(m + 1), ("a", "b", "c", "d"))


def test_heavy_tailed_durations_take_the_bounded_search():
    """Many intervals in one grid cell only widen the cell's window: the
    lookup binary-searches it in ceil(log2(grid_k + 1)) steps, fewer than
    a search of every end, with the reference's results."""
    tl = _heavy_tailed_timeline()
    dtl = tl.to_device()
    assert dtl.grid_k > 32
    assert dtl.lookup_steps == math.ceil(math.log2(dtl.grid_k + 1))
    assert dtl.lookup_steps < math.ceil(math.log2(len(tl.ends) + 1))
    spec = InstantTraceSensor.make_spec()
    res = dp.run_region_pipeline(dtl, spec, period=5e-3, jitter=100e-6,
                                 seed=2, chunk_size=512)
    ref = dp.reference_region_pipeline(tl, spec, period=5e-3,
                                       jitter=100e-6, seed=2,
                                       chunk_size=512)
    assert res.n == ref.n
    _assert_stats_close((res.counts, res.psum, res.psumsq),
                        (ref.counts, ref.psum, ref.psumsq))


def _count_le_all(dtl, t):
    """``_count_le`` as the pipeline calls it: vmapped over workers, the
    sample times shared, jitted under x64. Returns [W, len(t)]."""
    import jax
    import jax.numpy as jnp
    from jax import enable_x64
    count = jax.jit(jax.vmap(dp._count_le, in_axes=(0, 0, 0, None, None)),
                    static_argnums=4)
    with enable_x64():
        return np.asarray(count(dtl.ends, dtl.grid, dtl.cell,
                                jnp.asarray(t, jnp.float64), dtl.grid_k))


def _burst_timeline(b, seed=0, n_long=40, tiny=1e-7):
    """Long intervals with a burst of ``b`` tiny ones in their middle:
    the burst and the long interval before it share a cell."""
    rng = np.random.default_rng(seed)
    longs = rng.uniform(0.5, 1.5, n_long)
    d = np.concatenate([longs[:n_long // 2], np.full(b, tiny),
                        longs[n_long // 2:]])
    m = len(d)
    return Timeline(np.arange(m) % 3, d, 50.0 + 10.0 * rng.random(m),
                    ("a", "b", "c"))


def _one_cell_timeline(m=200):
    """A long interval, then m - 1 of a nanosecond: every end falls in
    the last grid cell."""
    return Timeline(np.arange(m) % 3,
                    np.concatenate([[1.0], np.full(m - 1, 1e-9)]),
                    np.full(m, 60.0), ("a", "b", "c"))


_LOOKUP_CASES = {
    # name: (timelines, grid_k the case must reach)
    "k1": (lambda: [_burst_timeline(0)], 1),
    "k2": (lambda: [_burst_timeline(1)], 2),
    "k3": (lambda: [_burst_timeline(2)], 3),
    "k57_burst": (lambda: [_burst_timeline(56)], 57),
    "one_cell": (lambda: [_one_cell_timeline()], 200),
    "ragged_pair": (lambda: [_burst_timeline(56, seed=1),
                             _burst_timeline(3, seed=2, n_long=12)], 57),
}


@pytest.mark.parametrize("case", sorted(_LOOKUP_CASES))
def test_count_le_equals_searchsorted_exactly(case):
    """The grid-bounded search is ``searchsorted(side="right")`` bit for
    bit: at every interval end and one ulp either side, at every grid
    point, at 0 and the horizon, past the last interval, and at random
    times, for every worker of a ragged (``+inf``-padded) batch."""
    build, want_k = _LOOKUP_CASES[case]
    tls = build()
    dtl = dp.DeviceTimeline.from_timelines(tls)
    assert dtl.grid_k == want_k
    G = dtl.grid.shape[1] - 2
    t_max = max(tl.t_exec for tl in tls)
    q = [np.array([0.0, dtl.t_end, t_max, np.nextafter(t_max, np.inf)])]
    for tl in tls:
        q += [tl.ends, np.nextafter(tl.ends, -np.inf),
              np.nextafter(tl.ends, np.inf)]
    # Grid points exactly as from_timelines and the lookup form them.
    q += [np.arange(G + 2, dtype=np.float64) * c
          for c in np.asarray(dtl.cell)]
    q.append(np.random.default_rng(7).uniform(0.0, t_max, 2000))
    t = np.concatenate(q)
    got = _count_le_all(dtl, t)
    for w, tl in enumerate(tls):
        np.testing.assert_array_equal(
            got[w], np.searchsorted(tl.ends, t, side="right"))


def test_lookup_is_a_bounded_unrolled_search():
    """The traced lookup has no loop (a full ``searchsorted`` would bring
    a ``while``) and gathers ``ends`` once per search step."""
    import jax
    import jax.numpy as jnp
    from jax import enable_x64

    from repro.analysis.jaxpr_audit import iter_eqns
    dtl = _heavy_tailed_timeline().to_device()
    with enable_x64():
        jaxpr = jax.make_jaxpr(
            jax.vmap(dp._count_le, in_axes=(0, 0, 0, None, None)),
            static_argnums=4)(dtl.ends, dtl.grid, dtl.cell,
                              jnp.zeros(512, jnp.float64), dtl.grid_k)
    eqns = list(iter_eqns(jaxpr))
    assert not [e for e in eqns if e.primitive.name == "while"]
    ends_gathers = [
        e for e in eqns if e.primitive.name == "gather"
        and e.invars[0].aval.shape == dtl.ends.shape
        and e.invars[0].aval.dtype == np.float64]
    assert len(ends_gathers) == dtl.lookup_steps


def test_device_timeline_ragged_workers_pad():
    a = Timeline(np.array([0, 1]), np.array([1.0, 2.0]),
                 np.array([50.0, 100.0]), ("x", "y"))
    b = Timeline(np.array([1, 0, 1, 0]), np.array([0.5, 0.5, 1.0, 3.0]),
                 np.array([80.0, 60.0, 90.0, 70.0]), ("x", "y"))
    dtl = dp.DeviceTimeline.from_timelines([a, b])
    assert dtl.num_workers == 2
    assert dtl.ends.shape == (2, 4)
    assert dtl.t_end == pytest.approx(3.0)       # min worker horizon
    np.testing.assert_array_equal(np.asarray(dtl.m_true), [2, 4])
    assert np.isinf(np.asarray(dtl.ends)[0, 2])  # ragged pad
    # to_device() is the single-worker shorthand.
    assert a.to_device().num_workers == 1
    with pytest.raises(ValueError):
        dp.DeviceTimeline.from_timelines([])
    other = Timeline(np.array([0, 1]), np.array([1.0, 2.0]),
                     np.array([50.0, 100.0]), ("p", "q"))
    with pytest.raises(ValueError, match="name space"):
        dp.DeviceTimeline.from_timelines([a, other])


# ---------------------------------------------------------------------------
# Profiler wiring: device backend is the default, host stays the oracle.
# ---------------------------------------------------------------------------

def test_profiler_streaming_device_vs_host_accuracy():
    # Same workload/tolerances as test_profile_timeline_streaming_accuracy
    # (regions with enough samples for the 10–12% bands at this period).
    costs = [RegionCost("attn", flops=4e11, hbm_bytes=1.5e10, invocations=8),
             RegionCost("ffn", flops=9e11, hbm_bytes=2.5e10, invocations=8)]
    tl = synthesize(costs, steps=150, seed=5)
    prof = EnergyProfiler(period=10e-3, seed=6)
    est_dev = prof.profile_timeline_streaming(tl, sensor="rapl",
                                              chunk_size=1024,
                                              pipeline="device")
    est_host = prof.profile_timeline_streaming(tl, sensor="rapl",
                                               chunk_size=1024,
                                               pipeline="host")
    gt = ground_truth(tl)
    for name, g in gt.items():
        for est in (est_dev, est_host):
            r = est.by_name()[name]
            assert r.t_hat == pytest.approx(g["time"], rel=0.10)
            assert r.e_hat == pytest.approx(g["energy"], rel=0.12)


def test_profiler_auto_prefers_device_and_respects_overrides():
    (tl,) = _timelines(1)
    prof = EnergyProfiler(period=10e-3, seed=2)
    est_auto = prof.profile_timeline_streaming(tl, sensor="instant",
                                               chunk_size=1024)
    est_dev = prof.profile_timeline_streaming(tl, sensor="instant",
                                              chunk_size=1024,
                                              pipeline="device")
    # auto == device (bit-identical estimates: same fused path).
    assert est_auto.n_total == est_dev.n_total
    np.testing.assert_array_equal(est_auto.table.n_samples,
                                  est_dev.table.n_samples)
    np.testing.assert_array_equal(est_auto.table.e_hat, est_dev.table.e_hat)
    # An explicit host aggregate_fn implies the host chunk seam.
    seen = []

    def spy_agg(ids, pows, num_regions):
        seen.append(len(ids))
        from repro.core.estimator import aggregate_samples_np
        return aggregate_samples_np(ids, pows, num_regions)

    prof.profile_timeline_streaming(tl, sensor="instant", chunk_size=1024,
                                    aggregate_fn=spy_agg)
    assert seen, "aggregate_fn must route through the host path"
    with pytest.raises(ValueError):
        prof.profile_timeline_streaming(tl, pipeline="gpu")
    # Explicit device + host-seam aggregate_fn is a contradiction, not a
    # silent drop of the caller's kernel.
    with pytest.raises(ValueError, match="aggregate_fn"):
        prof.profile_timeline_streaming(tl, pipeline="device",
                                        aggregate_fn=spy_agg)


def test_sensor_instance_spec_matches_classmethod():
    """Instance .spec() carries instance parameters — the handle for
    driving the device pipeline with a customized sensor."""
    (tl,) = _timelines(1)
    assert InstantTraceSensor(tl).spec() == InstantTraceSensor.make_spec()
    assert RaplTraceSensor(tl, update_period=2e-3).spec() == \
        RaplTraceSensor.make_spec(update_period=2e-3)
    assert Ina231TraceSensor(tl, window=1e-3).spec() == \
        Ina231TraceSensor.make_spec(window=1e-3)
    res = dp.run_region_pipeline(
        tl.to_device(), RaplTraceSensor(tl, update_period=2e-3).spec(),
        period=10e-3, seed=0, chunk_size=2048)
    assert res.n > 0


def test_profiler_multiworker_device_matches_host_semantics():
    tls = _timelines(2, steps=120)
    prof = EnergyProfiler(period=10e-3)
    est, combos = prof.profile_multiworker_streaming(tls, sensor="instant",
                                                     chunk_size=256,
                                                     pipeline="device")
    assert len(combos) >= 2
    assert sum(r.t_hat for r in est.regions) == pytest.approx(
        min(t.t_exec for t in tls), rel=1e-6)


def test_device_result_merges_into_exchange_seams():
    """The fused result is a plain aggregator: shard merge with a host
    shard stays associative and exact."""
    from repro.core.streaming import StreamingAggregator
    (tl,) = _timelines(1)
    spec = InstantTraceSensor.make_spec()
    res = dp.run_region_pipeline(tl.to_device(), spec, period=10e-3, seed=4)
    dev_agg = StreamingAggregator.from_statistics(res.counts, res.psum,
                                                  res.psumsq)
    host_agg = StreamingAggregator(dev_agg.num_regions)
    host_agg.update([0, 1, 1], [10.0, 20.0, 30.0])
    merged = StreamingAggregator(dev_agg.num_regions)
    merged.merge(dev_agg).merge(host_agg)
    assert merged.n_total == res.n + 3
    np.testing.assert_allclose(
        merged.psum, res.psum + np.bincount(
            [0, 1, 1], weights=[10.0, 20.0, 30.0],
            minlength=dev_agg.num_regions))


# ---------------------------------------------------------------------------
# Benchmark entry point can't rot.
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_pipeline_benchmark_smoke(monkeypatch, tmp_path):
    import benchmarks.pipeline as bench
    monkeypatch.setenv("ALEA_BENCH_N", "20000")
    monkeypatch.setattr(bench, "_JSON_PATH",
                        tmp_path / "BENCH_pipeline.json")
    monkeypatch.setattr(bench, "WORKER_CONFIGS", (1, 4))
    rows = bench.run(verbose=False)
    assert rows and all(r.count(",") >= 2 for r in rows)
    assert (tmp_path / "BENCH_pipeline.json").exists()
