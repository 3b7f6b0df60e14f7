"""Share of the roofline the Pallas ``sample_attr`` reduction reaches.

The least time for the reduction's work in the traced window
(``bench.peaks.sample_attr_work``: per sample an i32 region id and C f32
channel powers read, per chunk R × (1 + 2C) f32 statistics written) over
the device time of the kernel's launches in the trace.

The program gives its kernels no name in the trace, so the launches are
found by their whole signature: a Pallas custom call whose result is the
kernel's ``f32[8, R']`` statistics block, R' the regions padded to the
kernel's region block (R itself up to 1,024 regions, else the next
multiple of 1,024), and whose launches number one per channel per chunk
of every call. A cell whose path runs no Pallas
kernel reads nothing. One that runs Pallas kernels of which none, or not
this many, match is an error: the metric would otherwise go silent or
count another kernel."""

import re

from bench.peaks import channels, sample_attr_work

KERNEL = re.compile(r"^\S+ f32\[8,(\d+)\] tpu_custom_call$")
BLOCK_R = 1024


class KernelNotFound(RuntimeError):
    pass


def kernel_ns(summary, *, regions: int, launches: int) -> float | None:
    """Device ns of the kernel's launches; ``None`` when the trace holds
    no Pallas kernel at all."""
    pallas = [k for k in summary.op_count if k.endswith(" tpu_custom_call")]
    if not pallas:
        return None
    width = -(-regions // BLOCK_R) * BLOCK_R if regions > BLOCK_R \
        else regions
    mine = [k for k in pallas if (m := KERNEL.match(k))
            and int(m.group(1)) == width]
    found = sum(summary.op_count[k] for k in mine)
    if found != launches:
        raise KernelNotFound(
            f"sample_attr: expected {launches} launches of an f32[8,R'] "
            f"Pallas call (R={regions}); found {found} among "
            f"{ {k: summary.op_count[k] for k in pallas} }")
    return sum(summary.op_ns[k] for k in mine)


def read(ctx):
    s, c = ctx.summary, ctx.counters
    if s is None or not c.get("calls"):
        return None
    launches = c["calls"] * c["chunks"] * channels(c["domains"])
    t = kernel_ns(s, regions=c["regions"], launches=launches)
    if t is None:
        return None
    work = sample_attr_work(samples=c["samples"], domains=c["domains"],
                            regions=c["regions"],
                            chunks=c["chunks"] * c["calls"])
    t_min, _ = work.least_time(ctx.peaks)
    return 100.0 * t_min / (t * 1e-9)
