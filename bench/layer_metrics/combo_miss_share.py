"""Share of the window's chunks that the combination pipeline folded
through the host: a chunk that holds a combination missing from the
device's table is replayed, its rows interned on the host and the table
rebuilt. From the program's own counts (``run_combo_pipeline``'s
``stats``: ``miss_chunks`` over ``chunks``)."""


def read(ctx):
    c = ctx.counters
    if not c.get("chunks") or "miss_chunks" not in c:
        return None
    return 100.0 * c["miss_chunks"] / c["chunks"]
