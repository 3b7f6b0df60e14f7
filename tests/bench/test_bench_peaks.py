"""Peaks by device kind, and the work counts behind the roofline
metrics, on hand-computed shapes."""

import pytest

from bench import peaks


def test_v5e_peaks_and_unknown_kind():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p.bf16_flops, p.hbm_bytes_per_s) == (197e12, 819e9)
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v9 imaginary")


def test_least_time_names_its_bound():
    p = peaks.peaks_for("TPU v5 lite")
    assert peaks.Work(flops=197e12, bytes=1.0).least_time(p) == (
        1.0, "compute")
    assert peaks.Work(flops=1.0, bytes=819e9).least_time(p) == (
        1.0, "memory")


def test_attribution_call_work_by_hand():
    # one worker, 10 intervals, D=3 (C=4), 100 samples, 5 rows
    w = peaks.attribution_call_work(intervals=10, domains=3, samples=100,
                                    rows=5)
    timeline = 10 * (8 + 4 + 3 * 8)            # 360
    samples = 100 * 2 * (4 + 4 * 4)            # 4000
    results = 5 * (8 + 2 * 8 * 4)              # 360
    assert w.bytes == timeline + samples + results
    assert w.flops == 100 * (1 + 3 * 4)


def test_sample_attr_work_by_hand():
    w = peaks.sample_attr_work(samples=1000, domains=3, regions=8, chunks=2)
    assert w.bytes == 1000 * (4 + 16) + 2 * 8 * 9 * 4
    assert peaks.sample_attr_work(samples=10, domains=1, regions=4,
                                  chunks=1).bytes == 10 * 8 + 4 * 3 * 4
