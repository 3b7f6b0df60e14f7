"""The control comes out not correct: the plain reference put in the
program's place at the precision below the one the configuration states
(float32 for the attribution's float64) fails one of the cell's limits.
At a size a test run can hold; ``bench/controls.py`` reads the same
control on the chip at the cell's own size."""

import numpy as np

from cells import tiny

from bench import manifest, timelines
from bench.drivers import attribution


def test_float32_attribution_control_fails_a_limit():
    cell = tiny()
    cfg = cell.config
    ref = manifest.reference(cell.config_name)
    runs = timelines.generate(cfg, cell.traffic, 2**31 + 9)
    want = attribution.reference_stats(ref, cfg, runs, 5)
    low = attribution.reference_stats(ref, cfg, runs, 5, dtype=np.float32)
    d = attribution.compare(low, want)
    assert any(d[k] > ref.LIMITS[k] for k in ref.LIMITS), d
    same = attribution.compare(want, want)
    assert all(v == 0.0 for v in same.values()), same
