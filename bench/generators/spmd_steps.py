"""Recorded steps replayed by the workers of an SPMD job with a barrier at
every step.

The traffic mix names the recording (``recording``, read by
``recorded_steps.load_recording``) and how long the profiled run is
(``samples_per_call`` sampling periods); the configuration names the
workers (``workers``) and the regions: the recording's operations plus one
``barrier`` region, numbered last. Every worker runs the same seeded order
of the recorded steps (rounds of seeded permutations, as
``recorded_steps`` makes them), and waits at a barrier when a step ends:

* step s is released at ``B_s`` (``B_0 = 0``); worker w starts it
  ``off[w, s]`` later, a half-normal launch skew of scale
  ``launch_skew_s``;
* each operation instance lasts its recorded duration times
  ``clip(1 + op_jitter·N(0, 1), *op_jitter_clip)``, drawn per worker;
* a worker holds ``barrier`` from the end of its step until the next
  release plus its next skew, so ``B_{s+1} = B_s + max_w(off[w, s] +
  step[w, s])``; after the last step every worker waits for the slowest.

The run keeps the steps whose release ends within ``samples_per_call``
periods, so the sample clock's blocks are not overrun. Rail powers per
region are dealt as ``recorded_steps`` deals them (``rail_power_w``);
``barrier`` takes each rail's lowest power; every instance varies by the
clipped normal factor ``power_noise``/``power_clip``. Intervals of no
length are dropped, so the workers' runs are ragged.
"""

from __future__ import annotations

import math

import numpy as np

from bench import manifest
from bench.timelines import Run

recorded_steps = manifest.generator("recorded_steps")


def generate(config: dict, traffic: dict, seed: int) -> list[Run]:
    labels, steps = recorded_steps.load_recording(traffic["recording"])
    W, R = config["workers"], config["regions"]
    if len(labels) + 1 != R:
        raise ValueError(f"recording {traffic['recording']!r} holds "
                         f"{len(labels)} regions and the barrier one more; "
                         f"the configuration says {R}")
    rng = np.random.default_rng([seed, 0])
    budget = traffic["samples_per_call"] * config["sampling"]["period_s"]
    per_round = sum(float(d.sum()) for _, d in steps)
    rounds = max(math.floor(budget / per_round), 1)
    order = np.concatenate([rng.permutation(len(steps))
                            for _ in range(rounds)])
    starts = np.cumsum([0] + [len(steps[k][0]) for k in order[:-1]])
    off = traffic["launch_skew_s"] * np.abs(
        rng.standard_normal((W, len(order))))
    recorded = np.concatenate([steps[k][1] for k in order])
    jlo, jhi = traffic["op_jitter_clip"]
    durs = recorded * np.clip(1.0 + traffic["op_jitter"]
                              * rng.standard_normal((W, len(recorded))),
                              jlo, jhi)
    busy = off + np.add.reduceat(durs, starts, axis=1)        # [W, steps]
    slowest = busy.max(axis=0)
    S = max(int(np.searchsorted(np.cumsum(slowest), budget,
                                side="right")), 1)
    n = durs.shape[1] if S == len(order) else int(starts[S])
    ids = np.concatenate([steps[k][0] for k in order[:S]])
    # Each step's ops follow one barrier interval, and one more ends the
    # run: worker w's wait before step s is the slowest worker's lead over
    # it in step s - 1, plus its own skew at step s.
    lead = slowest[None, :S] - busy[:, :S]
    waits = np.concatenate([off[:, :1], lead[:, :-1] + off[:, 1:S],
                            lead[:, -1:]], axis=1)             # [W, S + 1]
    L = n + S + 1
    is_op = np.ones(L, bool)
    is_op[starts[:S] + np.arange(S)] = False
    is_op[-1] = False
    all_ids = np.full(L, R - 1, np.int32)
    all_ids[is_op] = ids
    all_durs = np.empty((W, L))
    all_durs[:, is_op] = durs[:, :n]
    all_durs[:, ~is_op] = waits
    base = np.stack([np.append(rng.permutation(np.linspace(lo, hi, R - 1)),
                               lo)
                     for lo, hi in traffic["rail_power_w"]], axis=1)
    sig, (lo, hi) = traffic["power_noise"], traffic["power_clip"]
    noise = np.clip(1.0 + sig * rng.standard_normal((W, L)), lo, hi)
    runs = []
    for w in range(W):
        keep = all_durs[w] > 0.0
        rids = all_ids[keep]
        runs.append(Run(region_ids=rids, durations=all_durs[w, keep],
                        rails=base[rids] * noise[w, keep, None]))
    return runs
