"""Collective time per step during which no compute operation runs on
that device (ms), mean over the devices."""
from benchmarks.lib.names import is_train_step


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or ctx["chips"] < 2:
        return None
    steps = tr.steps(is_train_step)
    if not steps["n"]:
        return None
    return 1e3 * tr.exposed_collective_s() / steps["n_all"]
