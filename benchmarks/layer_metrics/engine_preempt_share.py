"""Preemptions per finished request in the window (%): preemptions /
finished, from DecodeEngine.stats(). A preempted request replays its
tokens, so each one costs output."""


def read(ctx):
    c = ctx.get("counters") or {}
    if not c.get("finished") or "preemptions" not in c:
        return None
    return 100.0 * c["preemptions"] / c["finished"]
