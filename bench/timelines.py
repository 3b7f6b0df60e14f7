"""The attribution cells' recorded runs, made by the generator that the
traffic mix names.

A recorded run is what the profiler attributes: per worker, a sequence of
basic-block intervals (region id, duration, one power per rail). A traffic
mix (``bench/traffic/<mix>.json``) names its generator under
``generator``: ``bench/generators/<generator>.py``, whose
``generate(config, traffic, seed)`` returns the runs. A mix of a new shape
adds a generator file and names it; no file here changes.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from bench import manifest


@dataclasses.dataclass(frozen=True)
class Run:
    """One worker's recorded run."""

    region_ids: np.ndarray   # int32 [m]
    durations: np.ndarray    # float64 [m] s
    rails: np.ndarray        # float64 [m, D] W

    @property
    def t_end(self) -> float:
        return float(np.sum(self.durations))


def generate(config: dict, traffic: dict, seed: int) -> list[Run]:
    return manifest.generator(traffic["generator"]).generate(
        config, traffic, seed)
