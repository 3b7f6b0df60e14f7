#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once on the chip this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (inputs and weights from the seed, compilation, warm-up) is timed
as ``setup_s``; then the cell's driver measures for ``--seconds``. With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from the profiler's trace of
the window and the benchmark's own spans and counters. After the window,
the driver compares what the timed path produced with the plain reference
(``bench/refs/<config>.py``); every number compared is printed beside its
limit as the last lines on standard error and under ``checks``, the last
key of the result.

The last line of standard output is the result, one JSON object. Without
a TPU whose ``device_kind`` has published peaks (``bench/peaks.py``), or
with fewer chips than the cell asks for, the run exits 2 and prints no
result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


class NoChip(RuntimeError):
    pass


@dataclasses.dataclass(frozen=True)
class LayerCtx:
    """What a per-layer reader may read."""

    summary: object | None       # bench.trace.DeviceSummary of the window
    counters: dict               # the driver's counts and host spans
    peaks: object                # bench.peaks.Peaks of this chip
    config: dict


def enable_compilation_cache() -> str:
    """JAX's persistent cache at a fixed path in the checkout, unless
    ``JAX_COMPILATION_CACHE_DIR`` names one. Every program is kept, so a
    later run of the cell compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def find_chip(chips: int):
    """The first device, if it is a TPU with published peaks and the
    process sees at least ``chips`` of them."""
    import jax

    from bench.peaks import UnknownDevice, peaks_for
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"needs a TPU; JAX's first device is {dev.platform!r}")
    if len(devices) < chips:
        raise NoChip(f"cell needs {chips} chip(s); JAX sees {len(devices)}")
    try:
        peaks = peaks_for(dev.device_kind)
    except UnknownDevice as e:
        raise NoChip(str(e)) from None
    return dev, len(devices), peaks


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, device=None, device_count: int = 1,
             peaks=None) -> dict:
    """Run the cell and return the result object. ``device``/``peaks``
    are found here unless given (tests give the host's CPU)."""
    from bench import harness, manifest

    cell = manifest.load_cell(workload, ROOT)
    if device is None:
        device, device_count, peaks = find_chip(cell.chips)
    driver = importlib.import_module(f"bench.drivers.{cell.config['kind']}")
    ctx = harness.Ctx(cell=cell, seed=seed, seconds=seconds, trace=trace,
                      t_start=t_start, device=device)
    out = driver.run(ctx)
    values = dict(out.metrics, setup_s=out.window_start - t_start)
    if trace:
        lctx = LayerCtx(summary=out.summary, counters=out.counters,
                        peaks=peaks, config=cell.config)
        metrics = {}
        for m in cell.per_layer:
            v = manifest.layer_reader(m["name"]).read(lctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    dev_info = {"platform": device.platform, "kind": device.device_kind,
                "count": device_count,
                "memory_peak_bytes": out.memory_peak_bytes}
    result = {"correct": all(c.ok for c in out.checks),
              "attempted": out.attempted, "failed": out.failed,
              "metrics": metrics, "device": dev_info}
    if trace and out.summary is not None:
        dev_info["busy_s"] = out.summary.busy_s
        dev_info["window_s"] = out.summary.window_s
        result["breakdown"] = out.summary.breakdown()
    result["counters"] = {k: v for k, v in out.counters.items()
                          if isinstance(v, (int, float))}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in out.checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    enable_compilation_cache()
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
