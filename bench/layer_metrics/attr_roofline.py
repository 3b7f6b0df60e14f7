"""Share of the roofline the fused attribution pipeline reaches.

The least time the chip needs for the work every call of the window must
do (``bench.peaks.attribution_call_work``: interval arrays read once, each
sample's region ids and channel powers written and read once, the
statistics written), over the device's busy time in the traced window,
in which it runs nothing but these calls. Memory bounds it at every size
the cells use."""

from bench.peaks import attribution_call_work


def read(ctx):
    s, c = ctx.summary, ctx.counters
    if s is None or s.busy_s <= 0 or not c.get("calls"):
        return None
    per_call = attribution_call_work(
        intervals=c["intervals"], domains=c["domains"],
        samples=c["samples"] / c["calls"], rows=c["rows"])
    t_min, _ = per_call.least_time(ctx.peaks)
    return 100.0 * c["calls"] * t_min / s.busy_s
