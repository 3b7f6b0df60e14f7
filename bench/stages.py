#!/usr/bin/env python3
"""Device time of the fused attribution call by stage.

The program runs each stage of its chunk step under a ``jax.named_scope``
``alea/<stage>`` (``core/device_pipeline.py``, ``kernels/sample_attr``):
``clock`` (sample times), ``lookup`` (interval counts), ``sensor`` (region
ids, sensor emulation, channel sums) and ``reduce`` (the carry update and
its Pallas ``sample_attr`` launches). The compiled program keeps the scope
path in each operation's ``op_name``, and the TPU's profiler keeps it in
the event metadata of each ``XLA Ops`` operation as the ``tf_op`` stat.
Scopes nest, and the last ``alea/`` component names the operation (XLA
joins the op names of instructions it merges: the fused binary searches
carry both of their call sites').

Here: the op name of each operation label in a traced window, the device
ns per stage inside it (control flow left out, ``unscoped`` for the
rest), and a stage's ns per sample. A trace in which no operation carries
a stage reads nothing; one whose stages cover less than ``COVERAGE`` of
the device time is an error, since a scope lost in a refactor would
otherwise show as a stage that got faster.

    python3 bench/stages.py --workload <cell> --seed <n> --seconds <s> \\
        [--fixture <out.json>]

runs the cell's driver with the profiler on, as ``bench/run.py --trace 1``
does, and prints one JSON line: the window's ``samples_per_s``, the stage
table and the costliest operations with their stage. ``--fixture`` also
writes the device operations, spans and op names of the window's first
call, the form of ``tests/bench/data/*_trace.json``.

No per-layer metric of ``BENCHMARK.json`` reads these numbers yet: the
harness's trace reduction (``bench/trace.py``) keeps no op names.
"""

from __future__ import annotations

import argparse
import collections
import importlib
import json
import pathlib
import re
import sys

STAGES = ("clock", "lookup", "sensor", "reduce")
UNSCOPED = "unscoped"
COVERAGE = 0.9
OP_NAME_STAT = "tf_op"
# A scope is a path component; a transform wraps it, as in vmap(alea/lookup),
# and the profiler ends an op name with ":".
_STAGE = re.compile(r"(?:^|[/(])alea/(" + "|".join(STAGES) + r")(?=$|[/):])")


class StageCoverage(RuntimeError):
    pass


def stage_of(op_name: str) -> str:
    """The innermost ``alea/<stage>`` scope of an op name."""
    found = _STAGE.findall(op_name)
    return found[-1] if found else UNSCOPED


def _message(buf: bytes, span: tuple[int, int] | None = None) -> dict:
    """Field number → values of one protobuf message in ``buf[span]``: an
    int for a varint, a ``(start, end)`` span for a length-delimited
    field; fixed-width fields are skipped."""
    i, end = span or (0, len(buf))
    out = collections.defaultdict(list)
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        else:
            i += {1: 8, 5: 4}[kind]
            continue
        out[key >> 3].append(v)
    return out


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        if b < 0x80:
            return v, i
        shift += 7


def _text(buf: bytes, field: list) -> str:
    """The first value of a string field, ``""`` where it is absent."""
    return buf[field[0][0]:field[0][1]].decode("utf-8", "replace") \
        if field else ""


def load_op_names(path: str) -> dict[str, str]:
    """Op name of every operation label (``bench.trace.op_label``) on the
    device planes of an ``.xplane.pb``: the ``OP_NAME_STAT`` stat of the
    op's event metadata. ``jax.profiler.ProfileData`` shows an event's
    own stats only, so the file is read here directly, by the field
    numbers of ``tsl/profiler/protobuf/xplane.proto``: XSpace.planes 1;
    XPlane.name 2, event_metadata 4, stat_metadata 5 (maps: key 1, value
    2); XEventMetadata.name 2, stats 5; XStat.metadata_id 1, str_value 5,
    ref_value 7 (a string interned as a stat metadata's name);
    XStatMetadata.id 1, name 2. One label with op names of two stages is
    an error: two programs shared it."""
    from bench import trace as T
    buf = pathlib.Path(path).read_bytes()
    names: dict[str, str] = {}
    for plane in _message(buf)[1]:
        p = _message(buf, plane)
        if not _text(buf, p[2]).startswith(T.DEVICE_PREFIX):
            continue
        values = {k: [_message(buf, _message(buf, e)[2][0])
                      for e in p[k] if _message(buf, e)[2]]
                  for k in (4, 5)}
        stat_names = {m[1][0] if m[1] else 0: _text(buf, m[2])
                      for m in values[5]}
        for m in values[4]:
            label = T.op_label(_text(buf, m[2]))
            for stat in m[5]:
                st = _message(buf, stat)
                if stat_names.get(st[1][0] if st[1] else 0) != OP_NAME_STAT:
                    continue
                op = _text(buf, st[5]) if st[5] else stat_names.get(
                    st[7][0] if st[7] else None, "")
                was = names.setdefault(label, op)
                if stage_of(was) != stage_of(op):
                    raise ValueError(f"{label!r} has op names {was!r} and "
                                     f"{op!r}")
    return names


def stage_ns(op_ns: dict[str, float],
             op_names: dict[str, str]) -> dict[str, float] | None:
    """Device ns per stage from device ns per operation label (the
    window's ``DeviceSummary.op_ns``, control flow left out); ``None``
    where no operation carries a stage."""
    tot: dict[str, float] = collections.defaultdict(float)
    for label, ns in op_ns.items():
        tot[stage_of(op_names.get(label, ""))] += ns
    if not set(tot) - {UNSCOPED}:
        return None
    return {s: tot.get(s, 0.0) for s in (*STAGES, UNSCOPED)}


def coverage(by_stage: dict[str, float]) -> float:
    total = sum(by_stage.values())
    return (total - by_stage[UNSCOPED]) / total if total > 0 else 0.0


def ns_per_sample(by_stage: dict[str, float] | None, samples: int,
                  stage: str) -> float | None:
    """A stage's device ns per attributed sample; ``None`` where the
    trace holds no stage at all."""
    if by_stage is None or samples <= 0:
        return None
    if coverage(by_stage) < COVERAGE:
        raise StageCoverage(
            f"the alea/ stages cover {coverage(by_stage):.1%} of the "
            f"device time, under {COVERAGE:.0%}: {by_stage}")
    return by_stage[stage] / samples


def first_call(tr):
    """The window cut to its first call: the device operations from the
    window's first one to the next launch of the same operation, and the
    spans that start among them; the window span becomes that stretch."""
    from bench import trace as T
    lo, hi = tr.window()
    ops = sorted((s, d, n) for evs in tr.device_ops.values()
                 for n, s, d in evs if lo <= s < hi)
    head = ops[0][2]
    end = next((s for s, _, n in ops[1:] if n == head), hi)
    start = ops[0][0]
    return T.Trace(
        device_ops={p: [(n, s, d) for n, s, d in evs if start <= s < end]
                    for p, evs in tr.device_ops.items()},
        spans=[(T.WINDOW_SPAN, start, end)] + [
            sp for sp in tr.spans
            if sp[0] != T.WINDOW_SPAN and start <= sp[1] < end])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fixture")
    args = ap.parse_args(argv)
    root = pathlib.Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root), str(root / "src")]

    from bench import harness, manifest
    from bench import run as bench_run
    from bench import trace as T

    bench_run.enable_compilation_cache()
    seen = {}
    load = T.load

    def load_with_op_names(path):
        # The harness deletes the trace once reduced: read it here too.
        tr = load(path)
        seen["trace"] = tr
        seen["op_names"] = load_op_names(path)
        return tr

    cell = manifest.load_cell(args.workload, root)
    try:
        device, _, _ = bench_run.find_chip(cell.chips)
    except bench_run.NoChip as e:
        print(f"stages: {e}", file=sys.stderr)
        return 2
    driver = importlib.import_module(f"bench.drivers.{cell.config['kind']}")
    T.load = load_with_op_names
    try:
        out = driver.run(harness.Ctx(cell=cell, seed=args.seed,
                                     seconds=args.seconds, trace=True,
                                     t_start=bench_run.T_START,
                                     device=device))
    finally:
        T.load = load
    s, names = out.summary, seen["op_names"]
    by_stage = stage_ns(s.op_ns, names)
    samples = out.counters["samples"]
    top = sorted(s.op_ns.items(), key=lambda kv: -kv[1])[:10]
    print(json.dumps({
        "samples_per_s": out.metrics["samples_per_s"],
        "correct": all(c.ok for c in out.checks), "samples": samples,
        "calls": out.counters["calls"], "busy_s": s.busy_s,
        "window_s": s.window_s, "stage_ns": by_stage,
        "coverage": by_stage and coverage(by_stage),
        "ns_per_sample": by_stage and {
            k: by_stage[k] / samples for k in by_stage},
        "top_ops": [[k, stage_of(names.get(k, "")), v * 1e-9,
                     s.op_count.get(k, 0)] for k, v in top]}))
    if args.fixture:
        tr = first_call(seen["trace"])
        labels = {n for evs in tr.device_ops.values() for n, _, _ in evs}
        c = out.counters
        pathlib.Path(args.fixture).write_text(json.dumps({
            "source": f"{device.device_kind}: the first of {c['calls']} "
                      f"calls in a window of bench/stages.py --workload "
                      f"{args.workload} --seed {args.seed} --seconds "
                      f"{args.seconds}",
            "device_ops": tr.device_ops, "spans": tr.spans,
            "op_names": {k: v for k, v in names.items() if k in labels},
            "samples_per_call": c["samples"] / c["calls"],
            **{k: c[k] for k in ("chunks", "domains", "regions")}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
