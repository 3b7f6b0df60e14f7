#!/usr/bin/env python3
"""Bring-up smoke run on a TPU, through the system's own entry points.

    python chip_smoke.py             # one chip: phases `pipeline`, `serve`
    python chip_smoke.py --chips 4   # four chips: phase `exchange` only

Phases:

* ``pipeline`` — the profiler's fused device pipeline:
  ``run_region_pipeline`` at D=1 and D=3 on a synthesized 1,024-region
  timeline of at least 2^20 RAPL samples, and ``run_combo_pipeline`` over
  W=16 phase-shifted workers. Each is compared with its numpy reference
  (``reference_region_pipeline`` / ``reference_combo_pipeline``): sample
  and region counts exactly, sums to ``SUM_RTOL``. Prints whether the
  compiled region run holds the Pallas reduction (``tpu_custom_call``).
* ``serve`` — qwen3-1.7b at its published widths (random weights from
  ``--seed``) behind ``serve.Engine`` with a ``PhaseEnergyAccountant``:
  8 requests, prompts of 32-256 tokens, 32 new tokens each. Every request
  must complete with its token count, the accountant must hold samples in
  ``serve/prefill`` and ``serve/decode``, and request 0 served alone by a
  fresh engine must give the same tokens.
* ``exchange`` — four ``run_region_pipeline`` shards, one per chip, on
  timelines whose powers sit on a 1/64 W grid read by the instant sensor
  (every partial sum exact, so the result may not depend on reduction
  order), all-reduced by ``collective_reduce`` over a 4-device exchange
  mesh and compared bit for bit with ``gather_shards`` over the same
  shards spilled through ``CheckpointExchange``.

Each phase prints its wall time, its compile time apart, the device's
peak bytes in use and the host sensor class the profiler picked. These
are bring-up diagnostics, not benchmark measurements.

Exits non-zero, without a result line, unless JAX's first device is a TPU
whose kind has published peaks (``repro.core.power_model.DEVICE_PEAKS``).
The last line of stdout is ``{"ok": true, "device": {"platform": ...,
"kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SUM_RTOL = 1e-5        # TPU: f32 per-chunk kernel sums folded into f64
REGIONS = 1024
PIPELINE_SAMPLES = 1 << 20
COMBO_WORKERS = 16
COMBO_SAMPLES = 1 << 18
EXCHANGE_SAMPLES = 1 << 18
PERIOD = 1e-3          # the RAPL counter's update period
SERVE_ARCH = "qwen3-1.7b"
SERVE_BATCH = 8
SERVE_MAX_LEN = 1024
SERVE_NEW_TOKENS = 32


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Sums JAX's own compile-duration events (tracing, lowering and
    backend compilation) so a phase reports compile time apart from
    its wall time."""

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.total += duration


def report_phase(name: str, t0: float, c0: float, clock: CompileClock,
                 dev, sensor: str, **extra) -> None:
    wall = time.perf_counter() - t0
    stats = dev.memory_stats() or {}
    fields = {"phase": name, "wall_s": wall,
              "compile_s": clock.total - c0,
              "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
              "host_sensor": sensor, **extra}
    print("PHASE " + json.dumps(fields), flush=True)


# ---------------------------------------------------------------------------
# Timelines
# ---------------------------------------------------------------------------


def region_timeline(seed: int, n_samples: int, *, domains: bool):
    """A synthesized step of REGIONS basic blocks, repeated until the
    horizon holds ``n_samples`` sampling periods."""
    from repro.core.timeline import RegionCost, synthesize
    rng = np.random.default_rng(seed)
    costs = [RegionCost(f"bb_{i}", flops=float(rng.uniform(1e11, 1e12)),
                        hbm_bytes=float(rng.uniform(5e8, 5e9)),
                        ici_bytes=float(rng.uniform(0.0, 2e8)),
                        invocations=4)
             for i in range(REGIONS)]
    t_step = synthesize(costs, steps=1, seed=seed).t_exec
    steps = math.ceil(n_samples * PERIOD * 1.02 / t_step)
    return synthesize(costs, steps=steps, seed=seed, domains=domains)


def combo_timelines(seed: int, n_samples: int):
    """W phase-shifted copies of one interval structure: §4.4
    barrier-synchronized workers, so the combination space is the
    transition patterns, not the R^W cross product."""
    from repro.core.timeline import Timeline
    rng = np.random.default_rng(seed)
    t_end = n_samples * PERIOD
    m = n_samples // 32
    durs = rng.uniform(0.5, 1.5, m) * (t_end / m)
    ids = rng.integers(0, REGIONS, m).astype(np.int32)
    pows = 50.0 + 150.0 * rng.random(m)
    names = tuple(f"bb_{i}" for i in range(REGIONS))
    tls = []
    for w in range(COMBO_WORKERS):
        off = (w / COMBO_WORKERS) * 0.5 * (t_end / m) + 1e-9
        tls.append(Timeline(np.concatenate([[ids[0]], ids]),
                            np.concatenate([[off], durs]),
                            np.concatenate([[pows[0]], pows]), names))
    return tls


def dyadic_timeline(seed: int, n_samples: int):
    """:func:`region_timeline` with powers on a 1/64 W grid. Read by the
    instant sensor, every partial sum of the statistics is then exact in
    float64, so any reduction order must reproduce the same bits — the
    premise of the collective-vs-checkpointed comparison."""
    from repro.core.timeline import Timeline
    tl = region_timeline(seed, n_samples, domains=False)
    return Timeline(tl.region_ids, tl.durations,
                    np.round(tl.powers * 64.0) / 64.0, tl.names)


def max_rel(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-300)))


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def phase_pipeline(args, dev, clock, sensor) -> None:
    from jax import enable_x64

    from repro.core import device_pipeline as dp
    from repro.core.sensors import RaplTraceSensor

    t0, c0 = time.perf_counter(), clock.total
    for d3 in (False, True):
        tl = region_timeline(args.seed, PIPELINE_SAMPLES, domains=d3)
        spec = RaplTraceSensor.make_spec(domains=tl.domain_names)
        kw = dict(period=PERIOD, seed=args.seed)
        dtl = tl.to_device()
        t_run = time.perf_counter()
        res = dp.run_region_pipeline(dtl, spec, **kw)
        t_run = time.perf_counter() - t_run
        with enable_x64():
            fn, call_args = dp.region_pipeline_call(dtl, spec, **kw)
            hlo = fn.lower(*call_args).compile().as_text()
        pallas = "tpu_custom_call" in hlo
        ref = dp.reference_region_pipeline(tl, spec, **kw)
        tag = f"region D={tl.num_domains}"
        check(pallas, f"{tag}: compiled run holds no tpu_custom_call")
        check(res.n == ref.n and res.n >= PIPELINE_SAMPLES,
              f"{tag}: n={res.n} vs reference {ref.n}")
        check(np.array_equal(res.counts, ref.counts),
              f"{tag}: counts differ from the reference in "
              f"{int(np.sum(res.counts != ref.counts))} regions")
        errs = {"psum": max_rel(res.psum, ref.psum),
                "psumsq": max_rel(res.psumsq, ref.psumsq),
                "rail_psum": max_rel(res.rail_psum, ref.rail_psum),
                "rail_psumsq": max_rel(res.rail_psumsq, ref.rail_psumsq)}
        check(max(errs.values()) <= SUM_RTOL,
              f"{tag}: sums off the reference by {errs} > {SUM_RTOL}")
        print(f"pipeline {tag}: regions={len(tl.names)} "
              f"intervals={len(tl.region_ids)} samples={res.n} "
              f"tpu_custom_call={pallas} counts_match=True "
              f"max_rel_err={max(errs.values())!r} run_s={t_run!r}",
              flush=True)

    tls = combo_timelines(args.seed, COMBO_SAMPLES)
    spec = RaplTraceSensor.make_spec()
    kw = dict(period=PERIOD, seed=args.seed)
    stats: dict = {}
    t_run = time.perf_counter()
    agg, n = dp.run_combo_pipeline(dp.DeviceTimeline.from_timelines(tls),
                                   spec, stats=stats, **kw)
    t_run = time.perf_counter() - t_run
    ragg, rn = dp.reference_combo_pipeline(tls, lambda tl: spec, **kw)
    tag = f"combo W={COMBO_WORKERS}"
    check(n == rn, f"{tag}: n={n} vs reference {rn}")
    check(agg.interner.combos == ragg.interner.combos,
          f"{tag}: interned combinations differ from the reference")
    check(np.array_equal(agg.agg.counts, ragg.agg.counts),
          f"{tag}: counts differ from the reference")
    err = max(max_rel(agg.agg.psum, ragg.agg.psum),
              max_rel(agg.agg.psumsq, ragg.agg.psumsq))
    check(err <= SUM_RTOL, f"{tag}: sums off the reference by {err}")
    print(f"pipeline {tag}: combinations={len(agg.interner)} samples={n} "
          f"chunks={stats['chunks']} miss_chunks={stats['miss_chunks']} "
          f"counts_match=True max_rel_err={err!r} run_s={t_run!r}",
          flush=True)
    report_phase("pipeline", t0, c0, clock, dev, sensor)


def phase_serve(args, dev, clock, sensor) -> None:
    import jax

    from repro.configs.registry import get_config
    from repro.models import model as M
    from repro.serve.engine import (Engine, PhaseEnergyAccountant, Request,
                                    ServeConfig)

    t0, c0 = time.perf_counter(), clock.total
    cfg = get_config(SERVE_ARCH)
    params = jax.block_until_ready(
        M.init_params(jax.random.PRNGKey(args.seed), cfg))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    scfg = ServeConfig(max_batch=SERVE_BATCH, max_len=SERVE_MAX_LEN,
                       eos_token=-1)
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(1, cfg.vocab_size, int(n)).astype(np.int32)
               for n in rng.integers(32, 257, SERVE_BATCH)]

    def requests():
        return [Request(rid=i, prompt=p, max_new_tokens=SERVE_NEW_TOKENS)
                for i, p in enumerate(prompts)]

    reqs = requests()
    acct = PhaseEnergyAccountant(period=2e-3)
    t_serve = time.perf_counter()
    with acct:
        engine = Engine(cfg, params, scfg, accountant=acct)
        done = engine.run_until_drained(reqs)
    t_serve = time.perf_counter() - t_serve
    check(len(done) == len(reqs), f"served {len(done)}/{len(reqs)}")
    for r in reqs:
        check(r.done and r.status == "completed"
              and len(r.out_tokens) == SERVE_NEW_TOKENS,
              f"request {r.rid}: status={r.status} "
              f"tokens={len(r.out_tokens)}")
    tbl = acct.estimates().table
    samples = dict(zip(tbl.names, (int(x) for x in tbl.n_samples)))
    for phase in ("serve/prefill", "serve/decode"):
        check(samples.get(phase, 0) > 0,
              f"accountant holds no samples in {phase}: {samples}")
    del engine

    solo = requests()[0]
    Engine(cfg, params, scfg).run_until_drained([solo])
    check(solo.out_tokens == reqs[0].out_tokens,
          f"request 0 alone gave {solo.out_tokens[:8]}... vs batched "
          f"{reqs[0].out_tokens[:8]}...")
    print(f"serve {SERVE_ARCH}: params={n_params} layers={cfg.n_layers} "
          f"d_model={cfg.d_model} vocab={cfg.vocab_size} "
          f"max_batch={SERVE_BATCH} max_len={SERVE_MAX_LEN} "
          f"served={len(done)}/{len(reqs)} "
          f"prompt_tokens={sum(len(p) for p in prompts)} "
          f"new_tokens={sum(len(r.out_tokens) for r in reqs)} "
          f"solo_request0_match=True serve_s={t_serve!r} "
          f"accountant_samples={samples}", flush=True)
    report_phase("serve", t0, c0, clock, dev, sensor)


def phase_exchange(args, dev, clock, sensor) -> None:
    import jax

    from repro.core import device_pipeline as dp
    from repro.core.exchange import (CheckpointExchange, collective_reduce,
                                     gather_shards)
    from repro.core.sensors import InstantTraceSensor
    from repro.core.streaming import StreamingAggregator
    from repro.launch.mesh import make_exchange_mesh

    t0, c0 = time.perf_counter(), clock.total
    devices = jax.devices()[:args.chips]
    shards = []
    for h, d in enumerate(devices):
        tl = dyadic_timeline(args.seed + h, EXCHANGE_SAMPLES)
        spec = InstantTraceSensor.make_spec()
        with jax.default_device(d):
            dtl = tl.to_device()
            check(dtl.ends.devices() == {d},
                  f"shard {h} timeline not on {d}")
            res = dp.run_region_pipeline(dtl, spec, period=PERIOD,
                                         seed=args.seed + h)
        shards.append(StreamingAggregator.from_statistics(
            res.counts, res.psum, res.psumsq))
        print(f"exchange shard {h}: device={d.id} samples={res.n}",
              flush=True)
    coll = collective_reduce(shards, mesh=make_exchange_mesh(len(devices)))
    with tempfile.TemporaryDirectory() as spill:
        for h, s in enumerate(shards):
            CheckpointExchange(spill, host_id=h).spill(s)
        ckpt = gather_shards(spill)
    for name in ("counts", "psum", "psumsq"):
        a, b = getattr(coll, name), getattr(ckpt, name)
        check(np.array_equal(a, b),
              f"exchange: collective {name} differs from the checkpointed "
              f"gather in {int(np.sum(a != b))} rows")
    print(f"exchange: hosts={len(devices)} regions={len(coll.counts)} "
          f"samples={int(coll.counts.sum())} bit_exact=True", flush=True)
    report_phase("exchange", t0, c0, clock, dev, sensor)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the cross-chip exchange phase only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from repro.core.power_model import hardware_for
    from repro.core.sensors import available_host_sensor
    from repro.launch.cache import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{dev.platform!r}", file=sys.stderr)
        return 2
    check(len(devices) >= args.chips,
          f"--chips {args.chips} but JAX sees {len(devices)} device(s)")
    hw = hardware_for(dev.device_kind)
    sensor = type(available_host_sensor()).__name__
    print(f"device: platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devices)} peaks={hw.name} cache={cache_dir} "
          f"jax={jax.__version__}", flush=True)

    clock = CompileClock()
    phases = ([phase_exchange] if args.chips == 4
              else [phase_pipeline, phase_serve])
    for phase in phases:
        phase(args, dev, clock, sensor)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
