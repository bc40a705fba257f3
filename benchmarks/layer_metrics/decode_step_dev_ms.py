"""Device-busy time of one engine step (PagedDecoder._step_impl): the
time in which an operation ran on the device inside the step program's
executions of the traced window, over their number. The program's span
from start to end is longer: it holds the wait for the host's transfers
(the driver prints both on its "traced_steps" line)."""
from benchmarks.lib.names import is_decode_step


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    steps = tr.steps(is_decode_step)
    return 1e3 * steps["ops_s"] if steps["n"] else None
