#!/usr/bin/env python3
"""Record the device operations of a served model's decode steps: the
basic blocks, in time order, that the attribution cells' profiled program
runs.

    python3 bench/record_program.py --arch zamba2-1.2b --out <file.csv>

Runs the program's decode step of a published model at its published
widths (random weights from ``--seed``, ``--batch`` slots over a
``--max-len`` cache, each slot at its own depth) on the chip, traces
``--steps`` steps after a warm-up with the JAX profiler, and writes every
device operation of each step: the step, the operation's label (its HLO
name and result shape: one static instruction, as a basic block is one
static piece of code), and its start and duration in ns from the step's
first operation. Control flow, which spans the operations of its body, is
left out. The file is the data that ``bench/generators/recorded_steps.py``
replays; it is recorded once and committed.
"""

from __future__ import annotations

import argparse
import csv
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]


def step_rows(tr, span: str) -> list[tuple[int, str, int, int]]:
    """``(step, op label, start ns, duration ns)`` of every device
    operation of the first device that starts inside a host span named
    ``span``, start times from the step's first operation; control flow
    left out."""
    from bench import trace as T
    spans = sorted((s, e) for n, s, e in tr.spans if n == span)
    ops = sorted((s, d, name) for name, s, d in tr.device_ops[
        T.DEVICE_PREFIX + "0"] if not T.is_control_flow(name))
    rows = []
    for k, (lo, hi) in enumerate(spans):
        mine = [(s, d, n) for s, d, n in ops if lo <= s < hi]
        if not mine:
            raise ValueError(f"no device operation inside {span} {k}")
        t0 = mine[0][0]
        rows += [(k, n, int(round(s - t0)), int(round(d)))
                 for s, d, n in mine]
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--arch", default="zamba2-1.2b")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=2048)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import trace as T
    from repro.configs.registry import get_config
    from repro.models import model as M

    if jax.devices()[0].platform != "tpu":
        print("record_program: needs a TPU", file=sys.stderr)
        return 2
    cfg = get_config(args.arch)
    params = jax.jit(lambda k: M.init_params(k, cfg))(
        jax.random.PRNGKey(args.seed))
    cache = M.init_cache(cfg, args.batch, args.max_len)
    step = jax.jit(lambda p, t, c, n: M.decode_step(p, cfg, t, c, n))
    rng = np.random.default_rng(args.seed)
    depth = jnp.asarray(rng.integers(64, args.max_len // 2, args.batch),
                        jnp.int32)

    def one(cache, depth):
        tok = jnp.asarray(rng.integers(1, cfg.vocab_size, (args.batch, 1)),
                          jnp.int32)
        logits, cache = step(params, tok, cache, depth)
        jax.block_until_ready(logits)
        return cache, depth + 1

    for _ in range(3):
        cache, depth = one(cache, depth)
    tmp = tempfile.mkdtemp(prefix="record-")
    try:
        jax.profiler.start_trace(tmp)
        for _ in range(args.steps):
            with jax.profiler.TraceAnnotation(T.SPAN_PREFIX + "step"):
                cache, depth = one(cache, depth)
        jax.profiler.stop_trace()
        tr = T.load(T.find_xplane(tmp))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    rows = step_rows(tr, T.SPAN_PREFIX + "step")
    spans = {r[0] for r in rows}
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    with open(out, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["step", "op", "start_ns", "dur_ns"])
        w.writerows(rows)
    per_step = [sum(1 for r in rows if r[0] == k) for k in sorted(spans)]
    print(f"record_program: {args.arch} on {jax.devices()[0].device_kind}: "
          f"{len(spans)} steps, ops per step {per_step}, "
          f"{len({r[1] for r in rows})} distinct ops -> {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
