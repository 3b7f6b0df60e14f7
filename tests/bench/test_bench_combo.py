"""The combination cell on the CPU, at a size a test run holds: the SPMD
traffic it promises, its plain reference against the program's own
reference, a sound run that is correct, the float32 control and a broken
run that are not, and the numbers its per-layer metrics read."""

import copy
import dataclasses
import time
import types

import jax
import numpy as np
import pytest

from cells import cell

from bench import manifest, timelines
from bench import run as bench_run
from bench.combo_work import combination_call_work
from bench.drivers import attribution, combination
from bench.peaks import PEAKS
from bench.trace import DeviceSummary

CONFIG, TRAFFIC = "combo-call-rapl-w16", "spmd-barrier"
WORKLOAD = f"{CONFIG}.{TRAFFIC}"
BIG_SEED = 2**31 + 4242
BARRIER = 231

recorded_steps = manifest.generator("recorded_steps")


def tiny(workers: int = 4, samples: int = 20_000, block: int = 4096,
         checked: int = 2) -> manifest.Cell:
    """The cell cut to ``workers`` workers and ``samples`` sampling
    periods, drawn in ``block``-sample clock blocks."""
    c = cell(CONFIG, TRAFFIC)
    cfg, tr = copy.deepcopy(c.config), copy.deepcopy(c.traffic)
    cfg["workers"] = workers
    cfg["sampling"]["clock_block"] = block
    tr.update({"samples_per_call": samples, "checked_calls": checked})
    return dataclasses.replace(c, config=cfg, traffic=tr)


def _steps(run):
    """A worker's steps: ``(start, end, region ids, durations)`` of each
    stretch of operations between barrier intervals, on its own clock."""
    ends = np.cumsum(run.durations)
    op = (run.region_ids != BARRIER).astype(int)
    edge = np.flatnonzero(np.diff(np.concatenate([[0], op, [0]])))
    return [(ends[a] - run.durations[a], ends[b - 1], run.region_ids[a:b],
             run.durations[a:b]) for a, b in zip(edge[::2], edge[1::2])]


def test_spmd_workers_replay_one_order_and_meet_at_every_barrier():
    c = tiny()
    tr = c.traffic
    runs = timelines.generate(c.config, tr, BIG_SEED)
    assert len(runs) == c.config["workers"]
    steps = [_steps(r) for r in runs]
    S = len(steps[0])
    assert S > 100 and all(len(s) == S for s in steps)
    _, recorded = recorded_steps.load_recording(tr["recording"])
    lo, hi = tr["op_jitter_clip"]

    def within_clip(ids, durs):
        return [j for j, (rid, d) in enumerate(recorded)
                if np.array_equal(rid, ids)
                and lo - 1e-9 <= (durs / d).min()
                and (durs / d).max() <= hi + 1e-9]

    for k in range(S):
        ids = steps[0][k][2]
        # The same recorded step on every worker, each op within the clip.
        which = {tuple(within_clip(ids, s[k][3])) for s in steps}
        assert all(np.array_equal(s[k][2], ids) for s in steps)
        assert len(which) == 1 and len(next(iter(which))) == 1
    # Step k + 1 starts on every worker after the slowest one ended step
    # k, by its launch skew: a half-normal of scale launch_skew_s.
    skew = np.array([[s[k + 1][0] - max(t[k][1] for t in steps)
                      for k in range(S - 1)] for s in steps])
    sigma = tr["launch_skew_s"]
    assert skew.min() > -1e-12 and skew.max() < 6 * sigma
    assert 0.9 < skew.mean() / (sigma * np.sqrt(2 / np.pi)) < 1.1
    # Every worker waits for the slowest at the end; the run fits the
    # sample clock's periods, and one more step would not.
    t_end = np.array([r.t_end for r in runs])
    budget = tr["samples_per_call"] * c.config["sampling"]["period_s"]
    assert t_end.max() - t_end.min() < 1e-9
    assert budget - 0.012 < t_end.min() and t_end.max() <= budget
    assert all(np.all(r.durations > 0) for r in runs)
    assert all(r.rails.shape == (len(r.region_ids), 3) for r in runs)
    # The barrier draws each rail's lowest power, within the noise clip.
    bar = runs[0].rails[runs[0].region_ids == BARRIER]
    low = np.array([r[0] for r in tr["rail_power_w"]])
    assert np.all(bar >= 0.9 * low - 1e-9) and np.all(bar <= 1.1 * low)


def test_spmd_runs_are_seeded_and_hold_the_intervals_promised():
    c = tiny()
    a = timelines.generate(c.config, c.traffic, BIG_SEED)
    b = timelines.generate(c.config, c.traffic, BIG_SEED)
    o = timelines.generate(c.config, c.traffic, 99)
    for x, y in zip(a, b):
        assert np.array_equal(x.region_ids, y.region_ids)
        assert np.array_equal(x.durations, y.durations)
        assert np.array_equal(x.rails, y.rails)
    assert not np.array_equal(a[0].durations[:1000], o[0].durations[:1000])
    # Per worker: every step's operations, a barrier before each step and
    # one after the last, except for the worker that ended last.
    for run in a:
        s = _steps(run)
        ops = sum(len(x[2]) for x in s)
        assert len(run.region_ids) - ops - len(s) in (0, 1)
    lens = [len(r.region_ids) for r in a]
    assert max(lens) - min(lens) <= 1


def test_cells_run_is_the_profiled_run_it_promises():
    """At the cell's own length, 2^17 periods: about 3.04 M intervals a
    worker (two workers here; the cell's 16 hold 16 times as many)."""
    c = cell(CONFIG, TRAFFIC)
    cfg = copy.deepcopy(c.config)
    cfg["workers"] = 2
    runs = timelines.generate(cfg, c.traffic, BIG_SEED)
    n = c.traffic["samples_per_call"]
    samp = cfg["sampling"]
    assert n % samp["clock_block"] == 0
    for r in runs:
        assert 3.00e6 < len(r.region_ids) < 3.07e6
        assert (n - 12) * samp["period_s"] < r.t_end <= n * samp["period_s"]


def _program_stats(c, runs, seed):
    from repro.core import device_pipeline as dp
    from repro.core.sensors import RaplTraceSensor
    from repro.core.timeline import Timeline
    cfg = c.config
    domains = tuple(cfg["sensor"]["domains"])
    names = tuple(f"bb_{i}" for i in range(cfg["regions"]))
    tls = [Timeline(r.region_ids, r.durations, r.rails.sum(axis=1), names,
                    rail_powers=r.rails, domains=domains) for r in runs]
    spec = RaplTraceSensor.make_spec(cfg["sensor"]["update_period_s"],
                                     domains=domains)
    samp = cfg["sampling"]
    agg, n = dp.reference_combo_pipeline(
        tls, lambda tl: spec, period=samp["period_s"],
        jitter=samp["jitter_s"], seed=seed, chunk_size=samp["clock_block"])
    return combination._combo_stats(agg, n, cfg["workers"])


def test_combination_reference_matches_the_programs_reference():
    c = tiny()
    runs = timelines.generate(c.config, c.traffic, 2**31 + 3)
    ref = manifest.reference(c.config_name)
    want, = combination.reference_stats(ref, c.config, runs, [77])
    got = _program_stats(c, runs, 77)
    assert got[0] == want[0] > 15_000
    assert {k: v[0] for k, v in got[1].items()} == \
        {k: v[0] for k, v in want[1].items()}
    assert len(want[1]) > 500
    d = attribution.compare(got, want)
    assert d["count_mismatch"] == 0.0
    assert d["sum_gap_samples"] < 1e-6


def test_float32_combination_control_fails_a_limit():
    c = tiny()
    ref = manifest.reference(c.config_name)
    runs = timelines.generate(c.config, c.traffic, 2**31 + 9)
    want, = combination.reference_stats(ref, c.config, runs, [5])
    low, = combination.reference_stats(ref, c.config, runs, [5],
                                       dtype=np.float32)
    d = attribution.compare(low, want)
    assert any(d[k] > ref.LIMITS[k] for k in ref.LIMITS), d
    same = attribution.compare(want, want)
    assert all(v == 0.0 for v in same.values()), same


def run_tiny(monkeypatch, seconds=1.0):
    c = tiny()
    monkeypatch.setattr(manifest, "load_cell", lambda name, root=None: c)
    return bench_run.run_cell(WORKLOAD, 2**31 + 17, seconds, False,
                              t_start=time.perf_counter(),
                              device=jax.devices("cpu")[0],
                              peaks=PEAKS["TPU v5 lite"])


def test_sound_combination_run_is_correct(monkeypatch):
    res = run_tiny(monkeypatch)
    assert res["correct"], res["checks"]
    assert list(res["metrics"]) == ["samples_per_s", "setup_s"]
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"count_mismatch", "sum_gap_samples"}
    n = res["counters"]
    assert n["workers"] == 4 and n["calls"] >= 1
    assert n["chunks"] == 5 * n["calls"]
    assert 0 < n["miss_chunks"] <= n["chunks"]
    assert n["rows_interned"] <= n["samples"]
    assert n["table_rebuilds"] == n["miss_chunks"]
    assert n["distinct_combinations"] > 500 * n["calls"]
    assert {"memory_peak_bytes", "window_compiles", "setup_first_call_s",
            "intervals"} <= set(n)


def test_broken_combination_run_is_not_correct(monkeypatch):
    """One worker's region ids shifted by one in what the program
    returns."""
    from repro.core.streaming import StreamingCombinationAggregator
    call = combination.Program.__call__

    def shifted(self, root):
        agg, n, stats = call(self, root)
        mat = agg.interner.combo_matrix().copy()
        mat[:, 1] = (mat[:, 1] + 1) % self.dtl.num_regions
        counts, psum, psumsq = agg.agg.channel_statistics()
        return (StreamingCombinationAggregator.from_table(
            mat, counts, psum, psumsq, domains=agg.domains), n, stats)

    monkeypatch.setattr(combination.Program, "__call__", shifted)
    res = run_tiny(monkeypatch)
    assert res["correct"] is False
    assert res["checks"]["count_mismatch"]["value"] > 0


def test_window_runs_whole_calls_one_at_a_time(monkeypatch):
    """Calls run back to back, never two at once, on the counter roots
    drawn from the seed in turn."""
    call = combination.Program.__call__
    events = []

    def logged(self, root):
        events.append(("in", root))
        out = call(self, root)
        events.append(("out", root))
        return out

    monkeypatch.setattr(combination.Program, "__call__", logged)
    res = run_tiny(monkeypatch, seconds=0.5)
    assert res["correct"], res["checks"]
    roots = np.random.default_rng([2**31 + 17, 2])
    want = [int(roots.integers(0, 2**31 - 1))
            for _ in range(res["counters"]["calls"] + 1)]
    assert events == [(e, r) for r in want for e in ("in", "out")]


def test_window_runs_with_the_set_up_heap_frozen(monkeypatch):
    """The set-up's objects are frozen through the window's calls, the
    first (compiling) call excepted, and thawed once the window closes."""
    import gc
    call = combination.Program.__call__
    frozen = []

    def logged(self, root):
        frozen.append(gc.get_freeze_count())
        return call(self, root)

    monkeypatch.setattr(combination.Program, "__call__", logged)
    assert gc.get_freeze_count() == 0
    res = run_tiny(monkeypatch, seconds=0.5)
    assert res["correct"], res["checks"]
    assert frozen[0] == 0 and all(n > 0 for n in frozen[1:])
    assert len(frozen) == res["counters"]["calls"] + 1
    assert gc.get_freeze_count() == 0
    assert res["counters"]["setup_freeze_s"] >= 0


def test_combination_call_work_by_hand():
    # 2 workers, 10 intervals in all, D=3 (C=4), 100 samples, 5 rows
    w = combination_call_work(intervals=10, workers=2, domains=3,
                              samples=100, rows=5)
    timeline = 10 * (8 + 4 + 3 * 8)            # 360
    samples = 100 * (2 * 4 + 4 * 4)            # 2400
    results = 5 * (8 + 2 * 8 * 4)              # 360
    assert w.bytes == timeline + samples + results
    assert w.flops == 100 * (1 + 3 * 4)
    t, bound = w.least_time(PEAKS["TPU v5 lite"])
    assert bound == "memory" and t == pytest.approx(3120 / 819e9)


def _ctx(counters, busy_s=2.0, window_s=2.5, devices=1):
    """A traced window of ``devices`` chips of which one ran operations
    for ``busy_s`` seconds; the summary's ``busy_s`` is the mean over
    the devices."""
    op_ns = {"fusion.1": 0.75 * busy_s * 1e9, "fusion.2": 0.25 * busy_s * 1e9}
    summary = DeviceSummary(busy_s=busy_s / devices, window_s=window_s,
                            op_ns=op_ns, idle_by_span_ns={}, op_count={})
    return types.SimpleNamespace(summary=summary, counters=counters,
                                 peaks=PEAKS["TPU v5 lite"], config={})


def test_combination_layer_metrics_read_the_counters_and_the_trace():
    c = {"calls": 2, "samples": 262_000, "intervals": 48_600_000,
         "workers": 16, "domains": 3, "chunks": 4, "miss_chunks": 3,
         "distinct_combinations": 27_000}
    miss = manifest.layer_reader("combo_miss_share").read(_ctx(c))
    assert miss == pytest.approx(75.0)
    roof = manifest.layer_reader("combo_roofline").read(_ctx(c))
    work = combination_call_work(intervals=2 * 48_600_000, workers=16,
                                 domains=3, samples=262_000, rows=27_000)
    assert roof == pytest.approx(100 * work.bytes / 819e9 / 2.0)
    assert 0 < roof < 100
    idle = manifest.layer_reader("device_idle_share.combo").read(_ctx(c))
    assert idle == pytest.approx(20.0)
    # Nothing to read: no counters, no trace.
    bare = _ctx({"calls": 2})
    assert manifest.layer_reader("combo_miss_share").read(bare) is None
    assert manifest.layer_reader("combo_roofline").read(bare) is None
    untraced = types.SimpleNamespace(summary=None, counters=c, peaks=None,
                                     config={})
    assert manifest.layer_reader("combo_roofline").read(untraced) is None
    assert manifest.layer_reader(
        "device_idle_share.combo").read(untraced) is None


def test_combination_layer_metrics_read_the_busy_chip_of_a_host():
    c = {"calls": 2, "samples": 262_000, "intervals": 48_600_000,
         "workers": 16, "domains": 3, "chunks": 4, "miss_chunks": 3,
         "distinct_combinations": 27_000}
    one, four = _ctx(c), _ctx(c, devices=4)
    for name in ("combo_roofline", "device_idle_share.combo"):
        reader = manifest.layer_reader(name)
        assert reader.read(four) == pytest.approx(reader.read(one))
    assert manifest.layer_reader("device_idle_share.combo").read(
        four) == pytest.approx(20.0)


def test_benchmark_lists_the_combination_cell():
    c = manifest.load_cell(WORKLOAD)
    assert c.chips == 4 and c.config["kind"] == "combination"
    assert [m["name"] for m in c.end_to_end] == ["samples_per_s", "setup_s"]
    assert [m["name"] for m in c.per_layer] == [
        "combo_miss_share", "combo_roofline", "device_idle_share.combo"]
    assert c.config["workers"] == 16 and c.config["regions"] == 232
    assert c.config["max_combinations"] is None
