"""Driver of the combination cells: the profiler's multi-worker (§4.4)
combination pipeline, ``run_combo_pipeline``, the function that
``EnergyProfiler.profile_multiworker_streaming`` calls.

Set-up builds one recorded run of every worker from the seed with the
generator the traffic mix names (``bench/timelines.py``), uploads the
workers' timeline once, and makes one call that compiles (or loads from
the cache) every program the window uses; each part's seconds are counted
apart (``setup_*`` counters). The objects the set-up leaves are then
frozen (``gc.freeze``), so that a full collection in the window does not
rescan them: without it one collection of the whole process heap, about
0.1 s, falls in some windows and not in others. In the window whole calls
run back to back, each attributing the whole recorded run from a fresh
counter root (a fresh sample clock); a call waits on the host between its
device programs (the miss flag, the replayed chunk, the interning), and
that wait is part of the rate. The window closes after the last call that
started in its time. Once the window has closed, calls drawn from the seed
are recomputed by the plain reference and compared.

Counters: ``calls``, ``samples``, ``intervals`` (all workers), ``workers``
and the program's ``stats`` summed over the window's calls: ``chunks``,
``miss_chunks`` (chunks folded through the host), ``rows_interned``,
``table_rebuilds``, and ``distinct_combinations`` (the interner's size at
the end of each call). ``window_compiles`` counts the compilations in the
window, which should be none.
"""

from __future__ import annotations

import gc
import time

import jax
import numpy as np

from bench import harness, manifest, timelines
from bench.drivers.attribution import compare

_STATS = ("chunks", "miss_chunks", "rows_interned", "table_rebuilds",
          "distinct_combinations")


def _combo_stats(agg, n: int, width: int) -> tuple[int, dict]:
    """The program's aggregator as ``(n, {combination: (count, Σp [C],
    Σp² [C])})``, the form of the reference's result."""
    counts, psum, psumsq = agg.agg.channel_statistics()
    rows = agg.interner.combo_matrix().reshape(-1, width)
    return n, {tuple(int(v) for v in rows[i]): (int(counts[i]), psum[i],
                                                 psumsq[i])
               for i in np.flatnonzero(counts)}


def reference_stats(ref, config: dict, runs, seeds,
                    dtype=np.float64) -> list[tuple[int, dict]]:
    """The reference's statistics of the calls from counter roots
    ``seeds``, in the form of ``_combo_stats``."""
    samp = config["sampling"]
    return ref.attribute(
        [(r.region_ids, r.durations, r.rails) for r in runs],
        update=config["sensor"]["update_period_s"],
        period=samp["period_s"], jitter=samp["jitter_s"],
        block=samp["clock_block"], seeds=list(seeds), dtype=dtype)


class Program:
    """The system under test: the program's combination pipeline over the
    workers' uploaded recorded runs."""

    def __init__(self, config: dict, runs):
        from repro.core import device_pipeline as dp
        from repro.core.sensors import RaplTraceSensor
        from repro.core.timeline import Timeline

        self.dp = dp
        domains = tuple(config["sensor"]["domains"])
        names = tuple(f"bb_{i}" for i in range(config["regions"]))
        self.dtl = dp.DeviceTimeline.from_timelines([
            Timeline(r.region_ids, r.durations, r.rails.sum(axis=1), names,
                     rail_powers=r.rails, domains=domains) for r in runs])
        jax.block_until_ready(self.dtl.arrays())
        self.spec = RaplTraceSensor.make_spec(
            config["sensor"]["update_period_s"], domains=domains)
        samp = config["sampling"]
        self.kw = dict(period=samp["period_s"], jitter=samp["jitter_s"],
                       chunk_size=samp["clock_block"],
                       max_combinations=config.get("max_combinations"))

    def __call__(self, root: int):
        """One call from counter root ``root``: ``(aggregator, samples,
        stats)``."""
        stats: dict = {}
        agg, n = self.dp.run_combo_pipeline(self.dtl, self.spec, seed=root,
                                            stats=stats, **self.kw)
        return agg, n, {k: stats[k] for k in _STATS}


def run(ctx: harness.Ctx) -> harness.Outcome:
    cfg, tr = ctx.config, ctx.traffic
    if len(tr["rail_power_w"]) != len(cfg["sensor"]["domains"]):
        raise ValueError("traffic rail powers do not match the sensor's "
                         "domains")
    clock = [("start", ctx.t_start), ("runtime", time.perf_counter())]
    runs = timelines.generate(cfg, tr, ctx.seed)
    if len(runs) != cfg["workers"]:
        raise ValueError(f"generated {len(runs)} workers; the "
                         f"configuration says {cfg['workers']}")
    clock.append(("generate", time.perf_counter()))
    program = Program(cfg, runs)
    clock.append(("upload", time.perf_counter()))
    roots = np.random.default_rng([ctx.seed, 2])

    def next_root() -> int:
        return int(roots.integers(0, 2**31 - 1))

    program(next_root())              # compiles every program the window uses
    clock.append(("first_call", time.perf_counter()))
    gc.collect()
    gc.freeze()     # the set-up's objects, out of the window's collections
    clock.append(("freeze", time.perf_counter()))

    compiles = []

    def on_event(event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    results = []
    win = harness.Window(ctx)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        with win.measure():
            while time.perf_counter() - win.t0 < ctx.seconds:
                root = next_root()
                with ctx.span("call"):
                    results.append((root, *program(root)))
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
        gc.unfreeze()
    peak = harness.peak_bytes(ctx.device)

    W = cfg["workers"]
    samples = sum(n for _, _, n, _ in results)
    ref = manifest.reference(ctx.cell.config_name)
    pick = np.random.default_rng([ctx.seed, 3]).choice(
        len(results), size=min(tr["checked_calls"], len(results)),
        replace=False)
    checked = [results[i] for i in sorted(pick)]
    worst: dict[str, float] = {}
    for (_, agg, n, _), want in zip(checked, reference_stats(
            ref, cfg, runs, [root for root, *_ in checked])):
        d = compare(_combo_stats(agg, n, W), want)
        worst = {k: max(worst.get(k, 0.0), v) for k, v in d.items()}
    checks = [harness.Check(k, worst[k], ref.LIMITS[k]) for k in ref.LIMITS]
    counters = {"calls": len(results), "samples": samples,
                "intervals": sum(len(r.region_ids) for r in runs),
                "workers": W, "domains": len(cfg["sensor"]["domains"]),
                "regions": cfg["regions"],
                "call_s": win.seconds / len(results),
                "window_compiles": len(compiles),
                "memory_peak_bytes": peak,
                "sum_rel_err": worst["sum_rel_err"]}
    counters.update({k: sum(s[k] for *_, s in results) for k in _STATS})
    counters.update({f"setup_{b}_s": t1 - t0
                     for (_, t0), (b, t1) in zip(clock, clock[1:])})
    return harness.Outcome(
        metrics={"samples_per_s": samples / win.seconds},
        checks=checks, attempted=len(results), failed=0,
        memory_peak_bytes=peak, counters=counters, window_start=win.t0,
        summary=win.summary)
