"""The plain reference agrees with the program at a small size on the
CPU: the attribution statistics are equal."""

from cells import tiny

from bench import manifest, timelines
from bench.drivers import attribution


def test_attribution_reference_matches_the_program():
    cell = tiny()
    runs = timelines.generate(cell.config, cell.traffic, 2**31 + 3)
    program = attribution.Program(cell.config, runs[0])
    got = attribution._region_stats(program(77))
    want = attribution.reference_stats(
        manifest.reference(cell.config_name), cell.config, runs, 77)
    d = attribution.compare(got, want)
    assert d["count_mismatch"] == 0.0
    assert d["sum_gap_samples"] < 1e-6
    assert got[0] > 15_000
