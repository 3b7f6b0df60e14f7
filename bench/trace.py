"""Reduction of a profiler trace to device metrics.

The JAX profiler writes an ``.xplane.pb``. From it this takes the device
operations (the ``XLA Ops`` line of every ``/device:TPU:<n>`` plane) and
the benchmark's own host spans (``jax.profiler.TraceAnnotation`` names
starting with ``bench/``). Host and device events share one clock in the
file, so an idle gap on the device can be labelled by the host span that
covers it.

Busy time is the union of the intervals in which an operation ran on a
device; idle share is one minus busy over the traced window, averaged over
the devices used.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

import numpy as np

SPAN_PREFIX = "bench/"
WINDOW_SPAN = "bench/window"
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"


def op_label(hlo: str) -> str:
    """A short label for an ``XLA Ops`` event, whose name is the HLO
    instruction's text: its name, result shape (layouts dropped) and,
    for a custom call, its target, e.g. ``body.63 f32[8,1024]
    tpu_custom_call``."""
    name, _, rest = hlo.partition(" = ")
    end = rest.find(") ") + 1 if rest.startswith("(") else rest.find(" ")
    shape = re.sub(r"\{[^{}]*\}|/\*[^*]*\*/| ", "", rest[:end]) \
        if end > 0 else ""
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    return " ".join(filter(None, [name.lstrip("%"), shape,
                                  target.group(1) if target else ""]))


def is_control_flow(label: str) -> bool:
    """A while or conditional spans the operations of its body, which the
    line lists too."""
    return label.startswith(("while", "conditional"))


@dataclasses.dataclass
class Trace:
    """Events of one traced window, times in ns on the trace's clock."""

    device_ops: dict[str, list[tuple[str, float, float]]]  # plane → (name, start, dur)
    spans: list[tuple[str, float, float]]                  # (name, start, end)

    def window(self) -> tuple[float, float]:
        w = [(s, e) for n, s, e in self.spans if n == WINDOW_SPAN]
        if not w:
            raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
        return min(s for s, _ in w), max(e for _, e in w)


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {len(paths)}")
    return paths[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: dict[str, list] = {}
    spans = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(plane.name, []).extend(
                        (op_label(e.name), float(e.start_ns),
                         float(e.duration_ns)) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = float(e.start_ns)
                        spans.append((e.name, s, s + float(e.duration_ns)))
    return Trace(device_ops=ops, spans=spans)


def merge(intervals) -> np.ndarray:
    """Union of (start, end) intervals as a sorted disjoint [k, 2] array."""
    iv = np.asarray(intervals, np.float64).reshape(-1, 2)
    if len(iv) == 0:
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > reach[:-1]
    heads = np.flatnonzero(new)
    return np.stack([iv[heads, 0], np.maximum.reduceat(iv[:, 1], heads)],
                    axis=1)


def clip(merged: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if len(merged) == 0:
        return merged
    m = np.clip(merged, lo, hi)
    return m[m[:, 1] > m[:, 0]]


def busy_ns(trace: Trace, plane: str, lo: float, hi: float) -> float:
    iv = [(s, s + d) for _, s, d in trace.device_ops.get(plane, ())]
    m = clip(merge(iv), lo, hi)
    return float(np.sum(m[:, 1] - m[:, 0])) if len(m) else 0.0


def idle_gaps(trace: Trace, plane: str, lo: float, hi: float):
    """Gaps in the device's busy union inside [lo, hi], as (start, end)."""
    iv = [(s, s + d) for _, s, d in trace.device_ops.get(plane, ())]
    m = clip(merge(iv), lo, hi)
    edges = [lo] + [x for pair in m for x in pair] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def op_time_by_name(trace: Trace, lo: float, hi: float) -> dict[str, float]:
    """Device ns per operation label, over every device, inside [lo, hi];
    control flow, which spans other operations, left out."""
    tot: dict[str, float] = collections.defaultdict(float)
    for evs in trace.device_ops.values():
        for name, s, d in evs:
            if is_control_flow(name):
                continue
            e = min(s + d, hi)
            s = max(s, lo)
            if e > s:
                tot[name] += e - s
    return dict(tot)


def op_count_by_name(trace: Trace, lo: float, hi: float) -> dict[str, int]:
    """Launches per operation label, over every device, that start inside
    [lo, hi); control flow left out."""
    n: dict[str, int] = collections.defaultdict(int)
    for evs in trace.device_ops.values():
        for name, s, _ in evs:
            if lo <= s < hi and not is_control_flow(name):
                n[name] += 1
    return dict(n)


def label_gaps(gaps, spans) -> dict[str, float]:
    """Total gap ns by the innermost benchmark span open at each gap's
    midpoint (``host:other`` where none is). The window span itself labels
    nothing: it covers every gap. The spans come from one thread's
    ``with`` blocks, so they nest, and one sweep finds the innermost."""
    inner = sorted((s, e, n[len(SPAN_PREFIX):]) for n, s, e in spans
                   if n != WINDOW_SPAN)
    mids = sorted(((g0 + g1) / 2, g1 - g0) for g0, g1 in gaps)
    tot: dict[str, float] = collections.defaultdict(float)
    stack: list[tuple[float, str]] = []      # (end, label), innermost last
    i = 0
    for x, length in mids:
        while i < len(inner) and inner[i][0] <= x:
            s, e, n = inner[i]
            while stack and stack[-1][0] <= s:
                stack.pop()
            stack.append((e, n))
            i += 1
        while stack and stack[-1][0] <= x:
            stack.pop()
        tot[stack[-1][1] if stack else "host:other"] += length
    return dict(tot)


@dataclasses.dataclass(frozen=True)
class DeviceSummary:
    busy_s: float          # mean over devices
    window_s: float
    op_ns: dict[str, float]
    idle_by_span_ns: dict[str, float]
    op_count: dict[str, int]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_ns.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_span_ns.items(),
                      key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, v * 1e-9] for n, v in ops],
                "idle_gaps": [[n, v * 1e-9] for n, v in gaps]}


def summarize(trace: Trace) -> DeviceSummary:
    lo, hi = trace.window()
    planes = sorted(trace.device_ops)
    if not planes:
        raise ValueError("trace holds no device operations")
    busy = [busy_ns(trace, p, lo, hi) for p in planes]
    idle: dict[str, float] = collections.defaultdict(float)
    for p in planes:
        for k, v in label_gaps(idle_gaps(trace, p, lo, hi),
                               trace.spans).items():
            idle[k] += v / len(planes)
    return DeviceSummary(busy_s=float(np.mean(busy)) * 1e-9,
                         window_s=(hi - lo) * 1e-9,
                         op_ns=op_time_by_name(trace, lo, hi),
                         idle_by_span_ns=dict(idle),
                         op_count=op_count_by_name(trace, lo, hi))
