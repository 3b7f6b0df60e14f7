"""A run with the timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip (the host's CPU stands in,
at a tiny size) and drives the rest of a run of the cell, with one fault
planted in what the program produces: an answer altered where it is
produced, half of the work left out, or a step that returns its state
unchanged."""

import dataclasses
import time

import jax
import numpy as np
import pytest

from cells import WORKLOAD, tiny

from bench import manifest
from bench import run as bench_run
from bench.drivers import attribution
from bench.peaks import PEAKS


def run_tiny(monkeypatch, seconds):
    cell = tiny()
    monkeypatch.setattr(manifest, "load_cell", lambda name, root=None: cell)
    return bench_run.run_cell(WORKLOAD, 2**31 + 17, seconds, False,
                              t_start=time.perf_counter(),
                              device=jax.devices("cpu")[0],
                              peaks=PEAKS["TPU v5 lite"])


def test_sound_attribution_run_is_correct(monkeypatch):
    res = run_tiny(monkeypatch, 1.0)
    assert res["correct"], res["checks"]
    assert list(res["metrics"]) == ["samples_per_s", "setup_s"]
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"count_mismatch", "sum_gap_samples"}


def _altered(res):
    counts = res.counts.copy()
    r = int(np.flatnonzero(counts)[0])
    counts[r] -= 1
    counts[(r + 1) % len(counts)] += 1
    return dataclasses.replace(res, counts=counts)


def _half_left_out(res):
    return dataclasses.replace(res, counts=res.counts // 2,
                               psum=res.psum / 2, psumsq=res.psumsq / 2,
                               rail_psum=res.rail_psum / 2,
                               rail_psumsq=res.rail_psumsq / 2,
                               n=res.n // 2)


def _unchanged(res):
    z = np.zeros_like
    return dataclasses.replace(res, counts=z(res.counts), psum=z(res.psum),
                               psumsq=z(res.psumsq),
                               rail_psum=z(res.rail_psum),
                               rail_psumsq=z(res.rail_psumsq))


@pytest.mark.parametrize("fault", [_altered, _half_left_out, _unchanged])
def test_broken_attribution_is_not_correct(monkeypatch, fault):
    collect = attribution.Program.collect
    monkeypatch.setattr(attribution.Program, "collect",
                        lambda self, pending: fault(collect(self, pending)))
    res = run_tiny(monkeypatch, 1.0)
    assert res["correct"] is False
    assert res["checks"]["count_mismatch"]["value"] > 0
