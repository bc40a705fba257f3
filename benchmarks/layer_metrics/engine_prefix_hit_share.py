"""Share of the prompt pages admitted in the window that were attached
from the prefix index instead of computed: prefix_hit_pages /
(prefix_hit_pages + prefix_miss_pages), from DecodeEngine.stats()."""


def read(ctx):
    c = ctx.get("counters") or {}
    pages = c.get("prefix_hit_pages", 0) + c.get("prefix_miss_pages", 0)
    return 100.0 * c["prefix_hit_pages"] / pages if pages else None
