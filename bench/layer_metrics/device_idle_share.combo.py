"""Share of the traced window in which no operation ran on the chip that
runs the combination calls: the time it waits on the host's miss path
shows here. ``device_idle_share.attr``'s formula, with the busy time of
that chip (``bench.combo_work.device_busy_s``), since the cell's host
holds chips that run nothing."""

from bench.combo_work import device_busy_s


def read(ctx):
    s = ctx.summary
    if s is None or s.window_s <= 0:
        return None
    return 100.0 * (1.0 - device_busy_s(s) / s.window_s)
