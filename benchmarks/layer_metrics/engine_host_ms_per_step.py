"""Host time per engine step that the synchronous loop cannot hide behind
the device (ms): the admit, plan, dispatch and commit phases of
DecodeEngine.step() (its host_*_ns counters, written at the same
boundaries as the serving/* spans) over the window's steps. The sync
phase - the host waiting for the device - is left out."""

PHASES = ("host_admit_ns", "host_plan_ns", "host_dispatch_ns",
          "host_commit_ns")


def read(ctx):
    c = ctx.get("counters") or {}
    if not c.get("steps") or any(k not in c for k in PHASES):
        return None
    return sum(c[k] for k in PHASES) / c["steps"] / 1e6
