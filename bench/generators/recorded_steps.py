"""Recorded runs replayed from a program's recorded steps.

The traffic mix names a recording (``recording``, a CSV under
``bench/traffic/`` as ``bench/record_program.py`` writes it: step, op
label, start and duration in ns) and how long the profiled run is: as
many whole rounds of the recorded steps as ``samples_per_call`` sampling
periods hold, so that the sample clock's blocks are not overrun. Each
recorded step becomes a sequence of basic-block intervals: an operation
holds the program counter from its start to the next operation's start,
the last of a step for its own duration; operations that start together
leave intervals of no length, which are dropped. A region is one distinct
operation label, a static instruction, numbered in sorted order.

The run repeats the recorded steps in rounds, each round one seeded
permutation of them, so every seed gets the same set of intervals, and the
same length, in another order. The rail powers of each region come from
fixed evenly spaced sets dealt by a seeded permutation, and each instance
varies them by a clipped normal factor (``power_noise``, ``power_clip``).
"""

from __future__ import annotations

import csv
import functools
import math

import numpy as np

from bench import manifest
from bench.timelines import Run


@functools.lru_cache(maxsize=None)
def load_recording(name: str):
    """``(labels, steps)``: the sorted distinct operation labels, and per
    recorded step its intervals' region ids (int32) and durations (s)."""
    rows: dict[int, list] = {}
    with open(manifest.BENCH_DIR / "traffic" / name, newline="") as f:
        for r in csv.DictReader(f):
            rows.setdefault(int(r["step"]), []).append(
                (int(r["start_ns"]), int(r["dur_ns"]), r["op"]))
    labels = tuple(sorted({op for ops in rows.values() for *_, op in ops}))
    index = {op: i for i, op in enumerate(labels)}
    steps = []
    for k in sorted(rows):
        ops = sorted(rows[k])
        start = np.array([s for s, _, _ in ops], np.int64)
        end = np.append(start[1:], start[-1] + ops[-1][1])
        keep = end > start
        ids = np.array([index[op] for *_, op in ops], np.int32)
        steps.append((ids[keep], (end - start)[keep] * 1e-9))
    return labels, tuple(steps)


def generate(config: dict, traffic: dict, seed: int) -> list[Run]:
    labels, steps = load_recording(traffic["recording"])
    R = config["regions"]
    if len(labels) != R:
        raise ValueError(f"recording {traffic['recording']!r} holds "
                         f"{len(labels)} regions; the configuration says {R}")
    rng = np.random.default_rng([seed, 0])
    period = config["sampling"]["period_s"]
    per_round = sum(float(d.sum()) for _, d in steps)
    rounds = max(math.floor(traffic["samples_per_call"] * period / per_round),
                 1)
    order = np.concatenate([rng.permutation(len(steps))
                            for _ in range(rounds)])
    ids = np.concatenate([steps[k][0] for k in order])
    durs = np.concatenate([steps[k][1] for k in order])
    base = np.stack([rng.permutation(np.linspace(lo, hi, R))
                     for lo, hi in traffic["rail_power_w"]], axis=1)
    sig, (lo, hi) = traffic["power_noise"], traffic["power_clip"]
    noise = np.clip(1.0 + sig * rng.standard_normal(len(ids)), lo, hi)
    return [Run(region_ids=ids, durations=durs,
                rails=base[ids] * noise[:, None])]
