"""Published chip peaks, keyed by ``device_kind``, and the work counts the
roofline metrics divide by them.

Every count here comes from shapes alone, whatever implements the work: it
is the least the work needs, so a share of a roofline stays at or under
100% for any correct implementation.
"""

from __future__ import annotations

import dataclasses

PEAKS_SOURCE = ('Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
                '393 TOP/s int8, 16 GB HBM at 819 GB/s per chip')


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s
    int8_ops: float          # OP/s
    hbm_bytes_per_s: float   # B/s
    hbm_bytes: float         # B


PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, int8_ops=393e12,
                         hbm_bytes_per_s=819e9, hbm_bytes=16e9),
}


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(f"no published peaks for device kind "
                            f"{device_kind!r}; known: {sorted(PEAKS)}"
                            ) from None


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def least_time(self, peaks: Peaks) -> tuple[float, str]:
        """(seconds, which bound) on a chip with these peaks."""
        t_c = self.flops / peaks.bf16_flops
        t_m = self.bytes / peaks.hbm_bytes_per_s
        return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def channels(domains: int) -> int:
    """Statistic channels of a D-rail run: the rails, plus a total when
    D > 1."""
    return domains + (domains > 1)


def attribution_call_work(*, intervals: int, domains: int,
                          samples: float, rows: int) -> Work:
    """One fused attribution call over a recorded run of one worker.

    Reads the intervals once (end f64, region id i32, one power f64 per
    rail); writes and reads back each sample's region id (i32) and its
    channel powers (f32); writes the per-row statistics (count i64, Σp and Σp² f64 per channel). Operations: per
    sample and channel a square and two adds, and one add for the count.
    Lookup tables, energy prefix sums and padding are how an
    implementation does it, not what it needs, and are not counted.
    """
    c = channels(domains)
    timeline = intervals * (8 + 4 + 8 * domains)
    per_sample = 2 * (4 + 4 * c)
    results = rows * (8 + 2 * 8 * c)
    return Work(flops=float(samples * (1 + 3 * c)),
                bytes=float(timeline + samples * per_sample + results))


def sample_attr_work(*, samples: int, domains: int, regions: int,
                     chunks: int) -> Work:
    """The reduction alone: per sample an i32 region id and C f32 channel
    powers read; per chunk R × (1 + 2C) f32 statistics written. One add per
    statistic per sample and one multiply per channel for Σpow²."""
    c = channels(domains)
    return Work(flops=float(samples * (1 + 3 * c)),
                bytes=float(samples * (4 + 4 * c)
                            + chunks * regions * (1 + 2 * c) * 4))
