"""The flash-attention kernels' share of their roofline: the least time
for the causal forward + backward FLOPs of one step on one chip (FLOPs
bind: 3 x 2 T^2 d per layer and row at the bf16 peak) over the device time
of the kernels' events per step."""
from benchmarks.lib.names import is_flash_kernel, is_train_step


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    steps = tr.steps(is_train_step, is_flash_kernel)
    if not steps["n"] or steps["ops_s"] <= 0:
        return None
    least, _ = ctx["model"].flash_least_s(
        ctx["config"], ctx["rows_per_chip"], ctx["seq_len"], 2, ctx["peaks"])
    return 100.0 * least / steps["ops_s"]
