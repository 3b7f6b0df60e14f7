"""Seeded traffic repeats, every seed gets the same set of intervals in
another order, and the run replays the recording it names."""

import numpy as np
import pytest

from cells import CONFIG, TRAFFIC, cell, tiny

from bench import manifest, timelines

recorded_steps = manifest.generator("recorded_steps")

BIG_SEED = 2**31 + 12345


def test_generator_is_found_by_the_name_the_mix_gives():
    tr = cell(CONFIG, TRAFFIC).traffic
    assert manifest.generator(tr["generator"]).generate
    with pytest.raises(manifest.ManifestError):
        manifest.generator("no_such_generator")


def test_recorded_runs_repeat_and_keep_their_sizes():
    c = tiny(samples=60_000)
    a, = timelines.generate(c.config, c.traffic, BIG_SEED)
    b, = timelines.generate(c.config, c.traffic, BIG_SEED)
    o, = timelines.generate(c.config, c.traffic, 99)
    assert np.array_equal(a.region_ids, b.region_ids)
    assert np.array_equal(a.durations, b.durations)
    assert np.array_equal(a.rails, b.rails)
    assert len(a.region_ids) == len(o.region_ids)
    assert np.array_equal(np.sort(a.durations), np.sort(o.durations))
    assert not np.array_equal(a.durations, o.durations)
    assert a.t_end == pytest.approx(o.t_end, rel=1e-12)


def test_run_replays_every_recorded_step_in_rounds():
    c = tiny(samples=60_000)
    labels, steps = recorded_steps.load_recording(c.traffic["recording"])
    assert len(labels) == c.config["regions"]
    run, = timelines.generate(c.config, c.traffic, 7)
    per_round = sum(len(ids) for ids, _ in steps)
    rounds, rest = divmod(len(run.region_ids), per_round)
    assert rounds >= 1 and rest == 0
    want = np.sort(np.concatenate([ids for ids, _ in steps]))
    for k in range(rounds):
        got = run.region_ids[k * per_round:(k + 1) * per_round]
        assert np.array_equal(np.sort(got), want)
    assert (run.durations > 0).all()


def test_cells_run_is_the_profiled_run_it_promises():
    """Whole rounds of the recorded steps that fill the sample clock's
    blocks without overrunning them."""
    c = cell(CONFIG, TRAFFIC)
    _, steps = recorded_steps.load_recording(c.traffic["recording"])
    per_round = sum(float(d.sum()) for _, d in steps)
    run, = timelines.generate(c.config, c.traffic, BIG_SEED)
    samp = c.config["sampling"]
    n = c.traffic["samples_per_call"]
    assert n % samp["clock_block"] == 0
    assert (n - 1) * samp["period_s"] - per_round < run.t_end
    assert run.t_end <= n * samp["period_s"]
