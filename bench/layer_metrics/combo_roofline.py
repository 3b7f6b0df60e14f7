"""Share of the roofline the combination pipeline reaches.

The least time the chip needs for the work every call of the window must
do (``bench.combo_work.combination_call_work``: every worker's intervals
read once, each sample's region ids and channel powers written and read
once, the statistics of the distinct combinations written), over the
busy time of the chip that runs them in the traced window
(``bench.combo_work.device_busy_s``), in which it runs nothing but these
calls. Memory bounds it at the cell's size."""

from bench.combo_work import combination_call_work, device_busy_s


def read(ctx):
    s, c = ctx.summary, ctx.counters
    if s is None or device_busy_s(s) <= 0 or not c.get("calls") \
            or "distinct_combinations" not in c:
        return None
    work = combination_call_work(
        intervals=c["intervals"] * c["calls"], workers=c["workers"],
        domains=c["domains"], samples=c["samples"],
        rows=c["distinct_combinations"])
    t_min, _ = work.least_time(ctx.peaks)
    return 100.0 * t_min / device_busy_s(s)
