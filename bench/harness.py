"""What every driver shares: the run's context, spans, the measured window
and its trace, the checks that decide ``correct``, and the outcome."""

from __future__ import annotations

import contextlib
import dataclasses
import shutil
import tempfile
import time

from bench import trace as trace_mod
from bench.manifest import Cell


@dataclasses.dataclass(frozen=True)
class Check:
    """One number compared with its limit; the run is correct only if
    every check holds."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Ctx:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float               # perf_counter at process start
    device: object               # jax.Device the cell runs on

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    def span(self, name: str):
        """A host span in the profiler's trace (``bench/<name>``); costs
        nothing when the run is not traced."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(trace_mod.SPAN_PREFIX + name)


@dataclasses.dataclass
class Outcome:
    metrics: dict[str, float]        # end-to-end values the driver measured
    checks: list[Check]
    attempted: int
    failed: int
    memory_peak_bytes: int
    counters: dict                   # for the per-layer readers
    window_start: float              # perf_counter when the window opened
    summary: trace_mod.DeviceSummary | None = None


class Window:
    """The measured window: its host-clock bounds and, when traced, the
    device summary of the profiler's trace of it."""

    def __init__(self, ctx: Ctx):
        self.ctx = ctx
        self.t0 = self.t1 = None
        self.summary: trace_mod.DeviceSummary | None = None

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    @contextlib.contextmanager
    def measure(self):
        import jax
        tmp = tempfile.mkdtemp(prefix="bench-trace-") if self.ctx.trace \
            else None
        tracing = False
        try:
            if tmp:
                jax.profiler.start_trace(tmp)
                tracing = True
            with self.ctx.span("window"):
                self.t0 = time.perf_counter()
                yield self
                self.t1 = time.perf_counter()
            if tmp:
                tracing = False
                jax.profiler.stop_trace()
                self.summary = trace_mod.summarize(
                    trace_mod.load(trace_mod.find_xplane(tmp)))
        finally:
            if tracing:
                jax.profiler.stop_trace()
            if tmp:
                shutil.rmtree(tmp, ignore_errors=True)


def peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))
