#!/usr/bin/env python3
"""Readings that set the limits of ``correct`` in a combination cell, on
the chip, in one process.

    python bench/combo_controls.py --workload <cell> --seeds 1,2,3 --seconds 10

``bench/controls.py``'s method for the combination driver: for each seed a
run of the cell as ``bench/run.py`` does (the program's reading: the
numbers compared, as a sound run gives them) and, on the same inputs, the
control's reading: the plain reference put in the program's place at the
precision below the one the configuration states (float32 for the
float64). A limit lies between the largest sound reading and the smallest
control reading (PERF.md, section 2). The benchmark's own runs never run
the control.

Prints one JSON line per seed and a summary line last.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from run import ROOT, enable_compilation_cache  # noqa: E402  (bench/run.py)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    enable_compilation_cache()

    import run as bench_run
    from bench import manifest
    from bench.drivers import attribution, combination

    cell = manifest.load_cell(args.workload, ROOT)
    if cell.config["kind"] != "combination":
        raise SystemExit(f"not a combination cell: {cell.config['kind']!r}")
    control: dict[str, float] = {}
    sound = combination.reference_stats

    def both(ref_mod, config, runs, seeds, dtype=np.float64):
        want = sound(ref_mod, config, runs, seeds)
        low = sound(ref_mod, config, runs, seeds, dtype=np.float32)
        for lo, hi in zip(low, want):
            for k, v in attribution.compare(lo, hi).items():
                control[k] = max(control.get(k, 0.0), v)
        return want
    combination.reference_stats = both

    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        control.clear()
        t = time.perf_counter()
        res = bench_run.run_cell(args.workload, seed, args.seconds, False,
                                 t_start=t)
        row = {"seed": seed, "correct": res["correct"],
               "program": dict({k: c["value"] for k, c in
                                res["checks"].items()},
                               sum_rel_err=res["counters"]["sum_rel_err"]),
               "control": dict(control), "counters": res["counters"],
               "metrics": {k: m["value"] for k, m in res["metrics"].items()},
               "wall_s": time.perf_counter() - t}
        rows.append(row)
        print(json.dumps(row), flush=True)
    keys = rows[0]["program"]
    print(json.dumps({
        "workload": args.workload, "seeds": len(rows),
        "program_max": {k: max(r["program"][k] for r in rows) for k in keys},
        "control_min": {k: min(r["control"].get(k, float("nan"))
                               for r in rows) for k in control or keys}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
