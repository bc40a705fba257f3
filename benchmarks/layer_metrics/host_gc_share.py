"""Share of the window that Python's garbage collector held the
interpreter (%): gc_pause_ns of DecodeEngine.stats() (the process's
collections, every generation, timed by utils.stats.watch_gc's
gc.callbacks function) over the window. A program without the counter
(the parent's), or a window of no time, reads nothing."""


def read(ctx):
    c = ctx.get("counters") or {}
    if "gc_pause_ns" not in c or not ctx.get("window_s"):
        return None
    return 100.0 * c["gc_pause_ns"] / (ctx["window_s"] * 1e9)
