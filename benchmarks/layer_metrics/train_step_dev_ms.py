"""Device-busy time of one optimizer step: the time in which an operation
ran on the device inside the train step program's executions of the traced
window, over their number. Against the wall time per step it shows what
the feed and the host loop cost."""
from benchmarks.lib.names import is_train_step


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    steps = tr.steps(is_train_step)
    return 1e3 * steps["ops_s"] if steps["n"] else None
