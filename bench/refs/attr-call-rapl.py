"""Plain reference of sample → region attribution under a RAPL-class sensor.

Numpy, one chunk of the sample clock at a time, imports nothing of the
program. Semantics (ALEA §4, §4.5):

* sample clock: chunk k of ``block`` samples has times
  ``t_i = u0 + (k·block + i)·T + u_i``, ``u0 ~ U(0, T)`` drawn from
  ``fold_in(PRNGKey(seed), 0)`` and ``u_i ~ U(0, jitter)`` from
  ``fold_in(PRNGKey(seed), k + 1)`` (threefry, float64), rounded to an
  integer-nanosecond clock; samples at or past the end of the run do not
  count;
* program counter: the region at t is that of the interval holding t
  (interval ends are exclusive);
* RAPL counter: each rail's energy counter reads the exact integral of
  its power, refreshed every ``update`` seconds; a sample's power is the
  counter difference since the previous sample over the refresh-rounded
  time between them (at least one refresh), the first sample differenced
  against one refresh earlier; with several rails a total channel is
  appended;
* statistics: per region, the sample count and per channel Σp and Σp².

``dtype`` is the arithmetic of times and energies: float64 as the
configuration states; float32 is the control, the precision below it.
"""

from __future__ import annotations

import functools

import numpy as np

# count_mismatch: an exact comparison. sum_gap_samples: the largest
# difference of a region's sums in samples' worth; set from the readings
# in PERF.md, section 2.
LIMITS = {"count_mismatch": 0.0, "sum_gap_samples": 40.0}


@functools.lru_cache(maxsize=None)
def _draw_fn(block: int):
    import jax

    def draw(key, k, period, jitter):
        u0 = jax.random.uniform(jax.random.fold_in(key, 0), (), "float64",
                                0.0, period)
        u = jax.random.uniform(jax.random.fold_in(key, k + 1), (block,),
                               "float64", 0.0, jitter)
        return u0, u
    return jax.jit(draw)


def sample_times(seed: int, k: int, period: float, jitter: float,
                 block: int, dtype=np.float64) -> np.ndarray:
    """Chunk k's sample times. The uniform draws are threefry bits, drawn
    on the host CPU; the arithmetic on them is numpy's in ``dtype``."""
    import jax
    from jax import enable_x64
    with enable_x64(), jax.default_device(jax.devices("cpu")[0]):
        u0, u = _draw_fn(block)(jax.random.PRNGKey(seed), k, period, jitter)
        u0 = float(u0)
        u = np.asarray(u, np.float64)
    i = np.arange(k * block, (k + 1) * block, dtype=np.int64)
    t = (dtype(u0) + i.astype(dtype) * dtype(period) + u.astype(dtype))
    return (np.floor(t * dtype(1e9) + dtype(0.5)) * dtype(1e-9)).astype(dtype)


class _Worker:
    def __init__(self, region_ids, durations, rails, dtype):
        self.ids = np.asarray(region_ids)
        d = np.asarray(durations, np.float64)
        self.ends = np.cumsum(d).astype(dtype)
        self.bounds = np.concatenate([[0.0], np.cumsum(d)]).astype(dtype)
        r = np.asarray(rails, np.float64)
        self.rails = r.astype(dtype)
        self.energy = np.concatenate(
            [np.zeros((1, r.shape[1])), np.cumsum(d[:, None] * r, axis=0)]
        ).astype(dtype)
        self.m = len(d)

    def region_at(self, t):
        idx = np.clip(np.searchsorted(self.ends, t, side="right"),
                      0, self.m - 1)
        return self.ids[idx]

    def energy_at(self, x):
        x = np.clip(x, 0.0, self.bounds[-1])
        j = np.clip(np.searchsorted(self.bounds, x, side="right") - 1,
                    0, self.m - 1)
        return self.energy[j] + (x - self.bounds[j])[:, None] * self.rails[j]


def _rapl(worker, t, valid, prev, update, dtype):
    up = dtype(update)
    tq = (np.floor(t / up + dtype(1e-6)) * up).astype(dtype)
    prev_vec = np.concatenate([[prev], tq[:-1]]).astype(dtype)
    prev_vec = np.where(prev_vec < 0.0, np.maximum(tq - up, 0.0), prev_vec)
    dt = np.maximum(tq - prev_vec, up)
    p = (worker.energy_at(tq) - worker.energy_at(prev_vec)) / dt[:, None]
    new_prev = tq[valid][-1] if valid.any() else prev
    return p, new_prev


def attribute(run, *, regions: int, update: float, period: float,
              jitter: float, block: int, seed: int, dtype=np.float64):
    """Statistics of one recorded run ``(region_ids, durations, rails
    [m, D])``. Returns ``(n, stats)`` where ``stats`` maps a region id
    to ``(count, Σp [C], Σp² [C])``."""
    w = _Worker(*run, dtype)
    t_end = w.bounds[-1]
    D = w.rails.shape[1]
    C = D + (D > 1)
    n_chunks = max(int(np.ceil(float(t_end) / (block * period))), 1)
    counts = np.zeros(regions, np.int64)
    psum = np.zeros((regions, C))
    psumsq = np.zeros((regions, C))
    prev = dtype(-1.0)
    n = 0
    for k in range(n_chunks):
        t_raw = sample_times(seed, k, period, jitter, block, dtype)
        valid = t_raw < t_end
        t = np.minimum(t_raw, t_end)
        p, prev = _rapl(w, t, valid, prev, update, dtype)
        chan = np.asarray(p, np.float64)
        if C > D:
            chan = np.concatenate([chan, chan.sum(axis=1, keepdims=True)],
                                  axis=1)
        chan = chan[valid]
        n += int(valid.sum())
        rv = w.region_at(t)[valid]
        counts += np.bincount(rv, minlength=regions)
        for j in range(C):
            psum[:, j] += np.bincount(rv, chan[:, j], minlength=regions)
            psumsq[:, j] += np.bincount(rv, chan[:, j] ** 2,
                                        minlength=regions)
    return n, {r: (int(counts[r]), psum[r], psumsq[r])
               for r in np.flatnonzero(counts)}
