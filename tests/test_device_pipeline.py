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
    import jax
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
                                   *dtl.arrays(), jax.random.PRNGKey(0),
                                   jnp.int32(0), jnp.float64(1e-2),
                                   jnp.float64(2e-4),
                                   jnp.float64(dtl.t_end))
    assert all(buf.is_deleted() for buf in carry)
    assert not any(buf.is_deleted() for buf in new_carry[:3])


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
