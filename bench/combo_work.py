"""The least work of one combination attribution call (ALEA §4.4), for
the ``combo_roofline`` metric, counted from shapes alone as
``bench.peaks`` counts the single-worker call."""

from __future__ import annotations

from bench.peaks import Work, channels


def combination_call_work(*, intervals: int, workers: int, domains: int,
                          samples: float, rows: float) -> Work:
    """Combination attribution of ``samples`` samples over the recorded
    runs of ``workers`` workers, ``intervals`` intervals in all.

    Reads every worker's intervals once (end f64, region id i32, one power
    f64 per rail); writes and reads back each sample's region id per
    worker (i32) and its channel powers (f32) once; writes the statistics
    of the ``rows`` distinct combinations (count i64, Σp and Σp² f64 per
    channel). Operations: per sample and channel a square and two adds,
    and one add for the count. Packed keys, the combination table, lookup
    grids, energy prefix sums, padding and the replay of a chunk are how
    an implementation does it, not what it needs, and are not counted.
    """
    c = channels(domains)
    timeline = intervals * (8 + 4 + 8 * domains)
    per_sample = 4 * workers + 4 * c
    results = rows * (8 + 2 * 8 * c)
    return Work(flops=float(samples * (1 + 3 * c)),
                bytes=float(timeline + samples * per_sample + results))


def device_busy_s(summary) -> float:
    """Device seconds of the operations in the traced window. The calls
    run on one chip; where the host holds more (the cell takes a whole
    four-chip host), the others run nothing, and the summary's
    ``busy_s``, a mean over the devices that hold operations, would not
    be the busy time of that chip. Control flow, which spans other
    operations, is not counted twice."""
    return sum(summary.op_ns.values()) * 1e-9
