"""Share of the window's engine steps that were dispatched while the step
before was still in flight (%): steps_launched_ahead over steps, from
DecodeEngine.stats() over the window. The rest found nothing in flight (an
idle stretch ended, or the driver is synchronous) or landed the step in
flight first (ahead_drains: a draft, a spill store, a preemption). An
engine without the counter (its loop is serial), or a window without a
step, reads nothing."""


def read(ctx):
    c = ctx.get("counters") or {}
    if not c.get("steps") or "steps_launched_ahead" not in c:
        return None
    return 100.0 * c["steps_launched_ahead"] / c["steps"]
