"""Plain reference of sample → combination attribution (ALEA §4.4) under
a RAPL-class sensor summed over the workers.

Numpy, one chunk of the sample clock at a time, imports nothing of the
program. The sample clock and each worker's intervals and energy are the
attribution reference's (``bench/refs/attr-call-rapl.py``: the same
threefry draws, made by JAX on the host CPU). Here:

* program counters: at each sample, the region of every worker (a
  ``searchsorted`` of the sample time in each worker's interval ends);
  the samples run to the shortest worker's end;
* RAPL counter: each rail's energy counter reads the exact integral of
  that rail's power summed over the workers, refreshed every ``update``
  seconds, and differenced as in the attribution reference;
* statistics: per combination, the tuple of the workers' regions, the
  sample count and per channel (the rails, then their total) Σp and Σp².

``dtype`` is the arithmetic of times and energies: float64 as the
configuration states; float32 is the control, the precision below it.
"""

from __future__ import annotations

import numpy as np

from bench import manifest

# count_mismatch: an exact comparison. sum_gap_samples: the largest
# difference of a combination's sums in samples' worth; set from the
# readings in PERF.md, section 2.
LIMITS = {"count_mismatch": 0.0, "sum_gap_samples": 20.0}

_one = manifest.reference("attr-call-rapl")


def attribute(runs, *, update: float, period: float, jitter: float,
              block: int, seeds, dtype=np.float64) -> list:
    """Statistics of calls over one recorded run of W workers, each
    ``(region_ids, durations, rails [m, D])``: one call per counter root
    in ``seeds``. Each call's result is ``(n, stats)``, where ``stats``
    maps a combination (a tuple of W region ids) to ``(count, Σp [C],
    Σp² [C])``."""
    workers = [_one._Worker(*run, dtype) for run in runs]
    return [_call(workers, update, period, jitter, block, seed, dtype)
            for seed in seeds]


def _call(workers, update, period, jitter, block, seed, dtype):
    t_end = min(w.bounds[-1] for w in workers)
    up = dtype(update)
    n_chunks = max(int(np.ceil(float(t_end) / (block * period))), 1)
    stats: dict[tuple, list] = {}
    prev = dtype(-1.0)
    n = 0
    for k in range(n_chunks):
        t_raw = _one.sample_times(seed, k, period, jitter, block, dtype)
        valid = t_raw < t_end
        t = np.minimum(t_raw, t_end)
        tq = (np.floor(t / up + dtype(1e-6)) * up).astype(dtype)
        prev_vec = np.concatenate([[prev], tq[:-1]]).astype(dtype)
        prev_vec = np.where(prev_vec < 0.0, np.maximum(tq - up, 0.0),
                            prev_vec)
        dt = np.maximum(tq - prev_vec, up)
        energy = sum(w.energy_at(tq) - w.energy_at(prev_vec)
                     for w in workers)
        p = np.asarray(energy / dt[:, None], np.float64)
        chan = np.concatenate([p, p.sum(axis=1, keepdims=True)], axis=1)
        if valid.any():
            prev = tq[valid][-1]
        rows = np.stack([w.region_at(t) for w in workers], axis=1)[valid]
        chan = chan[valid]
        n += int(valid.sum())
        combos, inv = np.unique(rows, axis=0, return_inverse=True)
        inv = inv.reshape(-1)
        count = np.bincount(inv, minlength=len(combos))
        s1 = np.stack([np.bincount(inv, chan[:, j], len(combos))
                       for j in range(chan.shape[1])], axis=1)
        s2 = np.stack([np.bincount(inv, chan[:, j] ** 2, len(combos))
                       for j in range(chan.shape[1])], axis=1)
        for i, row in enumerate(map(tuple, combos.tolist())):
            if row in stats:
                c, a, b = stats[row]
                stats[row] = [c + int(count[i]), a + s1[i], b + s2[i]]
            else:
                stats[row] = [int(count[i]), s1[i], s2[i]]
    return n, {row: tuple(v) for row, v in stats.items()}
