"""Driver of the attribution cells: the profiler's fused device pipeline.

Set-up builds one recorded run from the seed with the generator the
traffic mix names (``bench/timelines.py``), uploads it, and makes one call
that compiles (or loads from the cache) every program the window uses;
each part's seconds are counted apart (``setup_*`` counters); a second
call, timed, sizes the queue, and the counter roots of the window's calls
are put on the device. In the window each call attributes the whole
recorded run from a fresh counter root (a fresh sample clock). The window
keeps about ``AHEAD_S`` seconds of calls dispatched ahead of the one whose
statistics it reads back, so that the chip stays fed while the host
stalls; when its time is up it dispatches nothing more, waits for every
call it sent, and reads the clock after that wait. Once the window has
closed, calls drawn from the seed are recomputed by the plain reference
and compared.
"""

from __future__ import annotations

import collections
import math
import time

import jax
import numpy as np
from jax import enable_x64

from bench import harness, manifest, timelines

# Seconds of calls in flight ahead of the one read back. On a v5e the
# runtime itself holds the host once ~32 calls are in flight (~6.4 s here).
AHEAD_S = 8.0


def _region_stats(res) -> tuple[int, dict]:
    chan = res.rail_psum
    sq = res.rail_psumsq
    if chan.shape[1] > 1:
        chan = np.concatenate([chan, res.psum[:, None]], axis=1)
        sq = np.concatenate([sq, res.psumsq[:, None]], axis=1)
    return int(res.n), {int(r): (int(res.counts[r]), chan[r], sq[r])
                        for r in np.flatnonzero(res.counts)}


def compare(got: tuple[int, dict], want: tuple[int, dict]) -> dict:
    """``count_mismatch``: samples counted in another row (or not at all),
    plus the difference in samples. ``sum_gap_samples``: the largest
    difference of any row's Σp or Σp² in samples' worth, that is over the
    reference's mean p (or p²) per sample of that channel.
    ``sum_rel_err``: the same differences relative to the row's own sums.
    A sample whose RAPL reading rounds to another counter refresh moves
    its row's sums by about one sample's worth, which is a large share of
    a row that few samples land in (PERF.md, section 2)."""
    n_got, g = got
    n_want, w = want
    mismatch = abs(n_got - n_want)
    rows = sorted(set(g) | set(w))
    for row in rows:
        mismatch += abs(g.get(row, (0,))[0] - w.get(row, (0,))[0])
    both = [r for r in rows if r in g and r in w]
    if not both:
        return {"count_mismatch": float(mismatch),
                "sum_gap_samples": math.inf, "sum_rel_err": math.inf}
    gap = rel = 0.0
    for j in (1, 2):                           # Σp, then Σp²
        a = np.array([g[r][j] for r in both], np.float64)
        b = np.array([w[r][j] for r in both], np.float64)
        d = np.abs(a - b)
        unit = np.abs(b).sum(axis=0) / max(n_want, 1)
        gap = max(gap, float(np.max(d / np.where(unit > 0, unit, 1.0))))
        rel = max(rel, float(np.max(np.where(
            b != 0, d / np.where(b != 0, np.abs(b), 1.0),
            np.where(d > 0, np.inf, 0.0)))))
    return {"count_mismatch": float(mismatch), "sum_gap_samples": gap,
            "sum_rel_err": rel}


def reference_stats(ref, config: dict, runs, seed: int,
                    dtype=np.float64) -> tuple[int, dict]:
    samp = config["sampling"]
    run = runs[0]
    return ref.attribute(
        (run.region_ids, run.durations, run.rails),
        regions=config["regions"],
        update=config["sensor"]["update_period_s"],
        period=samp["period_s"], jitter=samp["jitter_s"],
        block=samp["clock_block"], seed=seed, dtype=dtype)


class Program:
    """The system under test: the program's fused pipeline over one
    uploaded recorded run."""

    def __init__(self, config: dict, run):
        from repro.core import device_pipeline as dp
        from repro.core.sensors import RaplTraceSensor
        from repro.core.timeline import Timeline

        self.dp = dp
        domains = tuple(config["sensor"]["domains"])
        names = tuple(f"bb_{i}" for i in range(config["regions"]))
        tl = Timeline(run.region_ids, run.durations, run.rails.sum(axis=1),
                      names, rail_powers=run.rails, domains=domains)
        self.dtl = dp.DeviceTimeline.from_timelines([tl])
        self.spec = RaplTraceSensor.make_spec(
            config["sensor"]["update_period_s"], domains=domains)
        samp = config["sampling"]
        self.kw = dict(period=samp["period_s"], jitter=samp["jitter_s"],
                       chunk_size=samp["clock_block"])
        with enable_x64():
            self.fn, self.args = dp.region_pipeline_call(
                self.dtl, self.spec, **self.kw)
        self.root_arg = len(self.dtl.arrays())  # where the key goes in args

    @property
    def chunks(self) -> int:
        return self.dp.num_chunks(self.dtl.t_end, self.kw["period"],
                                  self.kw["chunk_size"])

    def key(self, root: int):
        """The counter root of one call on the device, made as
        ``region_pipeline_call`` makes it."""
        with enable_x64():
            return jax.random.PRNGKey(root)

    def dispatch(self, key):
        """Start one call of ``region_pipeline_call``'s program on its
        arguments, with ``key`` as the counter root; returns its device
        outputs without waiting for them."""
        i = self.root_arg
        with enable_x64():
            return self.fn(*self.args[:i], key, *self.args[i + 1:])

    def collect(self, pending):
        """Wait for a dispatched call; returns what ``run_region_pipeline``
        returns for it, on the host."""
        with enable_x64():
            counts, psum, psumsq, n = jax.device_get(pending)
        if int(n) == 0:
            raise ValueError("run too short for sampling period")
        return self.dp._result_from_channels(counts, psum, psumsq, int(n),
                                             self.dtl.t_end, self.dtl.domains)

    def __call__(self, root: int):
        """One call, waited for."""
        return self.collect(self.dispatch(self.key(root)))


def run(ctx: harness.Ctx) -> harness.Outcome:
    cfg, tr = ctx.config, ctx.traffic
    if len(tr["rail_power_w"]) != len(cfg["sensor"]["domains"]):
        raise ValueError("traffic rail powers do not match the sensor's "
                         "domains")
    clock = [("start", ctx.t_start), ("runtime", time.perf_counter())]
    runs = timelines.generate(cfg, tr, ctx.seed)
    if len(runs) != 1:
        raise ValueError("the attribution driver runs one worker")
    clock.append(("generate", time.perf_counter()))
    program = Program(cfg, runs[0])
    clock.append(("upload", time.perf_counter()))
    roots = np.random.default_rng([ctx.seed, 2])

    def next_root() -> int:
        return int(roots.integers(0, 2**31 - 1))

    program(next_root())              # compiles every program the window uses
    clock.append(("first_call", time.perf_counter()))
    t = time.perf_counter()
    program(next_root())
    call_s = time.perf_counter() - t
    ahead = min(max(math.ceil(AHEAD_S / call_s), 2), 256)
    # Every call's counter root is on the device before the window opens:
    # a root made in the window would queue beside the calls and hold the
    # host back long before ``ahead`` calls are in flight.
    n_keys = min(math.ceil(2 * (ctx.seconds + AHEAD_S) / call_s) + ahead,
                 4096)
    keys = collections.deque((r, program.key(r))
                             for r in (next_root() for _ in range(n_keys)))
    jax.block_until_ready([k for _, k in keys])
    clock.append(("queue", time.perf_counter()))

    results = []
    done: list[float] = []        # host clock at each read-back
    in_flight: collections.deque = collections.deque()

    def read_back():
        root, pending = in_flight.popleft()
        with ctx.span("read"):
            results.append((root, program.collect(pending)))
        done.append(time.perf_counter())

    win = harness.Window(ctx)
    with win.measure():
        while time.perf_counter() - win.t0 < ctx.seconds:
            if keys:
                root, key = keys.popleft()
            else:
                root = next_root()
                key = program.key(root)
            with ctx.span("dispatch"):
                in_flight.append((root, program.dispatch(key)))
            if len(in_flight) > ahead:
                read_back()
        while in_flight:
            read_back()
    peak = harness.peak_bytes(ctx.device)

    samples = sum(int(out.n) for _, out in results)
    ref = manifest.reference(ctx.cell.config_name)
    pick = np.random.default_rng([ctx.seed, 3]).choice(
        len(results), size=min(tr["checked_calls"], len(results)),
        replace=False)
    worst: dict[str, float] = {}
    for i in sorted(pick):
        root, out = results[i]
        d = compare(_region_stats(out), reference_stats(ref, cfg, runs, root))
        worst = {k: max(worst.get(k, 0.0), v) for k, v in d.items()}
    checks = [harness.Check(k, worst[k], ref.LIMITS[k]) for k in ref.LIMITS]
    # The longest wait between two read-backs, and when in the window it
    # ended: a call lasts ``call_s``, so a stall of the host or the chip
    # shows as the excess.
    gaps = np.diff([win.t0, *done])
    at = int(np.argmax(gaps[1:])) + 1 if len(gaps) > 1 else 0
    counters = {"calls": len(results), "samples": samples,
                "read_gap_max_s": float(gaps[at]),
                "read_gap_at_s": done[at] - win.t0, "call_s": call_s,
                "intervals": len(runs[0].region_ids),
                "domains": len(cfg["sensor"]["domains"]),
                "regions": cfg["regions"], "chunks": program.chunks,
                "rows": len(_region_stats(results[-1][1])[1]),
                "lookup_window": program.dtl.grid_k, "calls_ahead": ahead,
                "sum_rel_err": worst["sum_rel_err"]}
    counters.update({f"setup_{b}_s": t1 - t0
                     for (_, t0), (b, t1) in zip(clock, clock[1:])})
    return harness.Outcome(
        metrics={"samples_per_s": samples / win.seconds},
        checks=checks, attempted=len(results), failed=0,
        memory_peak_bytes=peak, counters=counters, window_start=win.t0,
        summary=win.summary)
