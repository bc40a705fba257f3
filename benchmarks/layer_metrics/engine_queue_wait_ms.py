"""Mean wait from submit to a slot (ms) of the requests first admitted in
the window: queue_wait_ns / admitted, from DecodeEngine.stats(). The part
of time to first token that is not prefill."""


def read(ctx):
    c = ctx.get("counters") or {}
    if not c.get("admitted") or "queue_wait_ns" not in c:
        return None
    return c["queue_wait_ns"] / c["admitted"] / 1e6
