"""The KDA state kernel's share of its roofline: the least time for its
work (every slot-step's float32 state over the KDA layers read and written
once at the HBM peak: ``state_rows_stepped``, a slot fed by several lanes
counted once a step, times the row's bytes; the count is the cell's model
file's) over the device time of the kernel's events, both of the traced
part. A program without the kernel or the counter (or a trace without the
kernel's events) reads nothing."""

#: ops/pallas_kda.py's ``kda_state_update`` as the trace reducer shows it
#: (benchmarks/lib/trace.short_name): matched exactly
KERNEL = "tpu_custom_call:kda_state_update"


def read(ctx):
    tr, c = ctx.get("trace"), ctx.get("traced_counters")
    if tr is None or not c or not c.get("state_rows_stepped"):
        return None
    kernel_s = tr.seconds_matching(lambda op: op == KERNEL)
    if kernel_s <= 0:
        return None
    return 100.0 * ctx["model"].state_least_s(
        ctx["config"], c, ctx["peaks"]) / kernel_s
