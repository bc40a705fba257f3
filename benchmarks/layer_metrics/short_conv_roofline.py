"""The short-convolution kernel's share of its roofline: the least time
for its work on the tails (every slot-step's float32 tails over the conv
layers read and written once at the HBM peak: ``state_rows_stepped``, a
slot fed by several lanes counted once a step, times the row's bytes; the
count is the cell's model file's) over the device time of the kernel's
events, both of the traced part. It reads low by construction: a
slot-step's tails are half a megabyte against 30 launches of the kernel,
and that is the finding it is there to state. A program without the kernel
or the counter (or a trace without the kernel's events) reads nothing."""

#: ops/pallas_kda.py's ``short_conv`` as the trace reducer shows it
#: (benchmarks/lib/trace.short_name): matched exactly
KERNEL = "tpu_custom_call:short_conv"


def read(ctx):
    tr, c = ctx.get("trace"), ctx.get("traced_counters")
    if tr is None or not c or not c.get("state_rows_stepped"):
        return None
    kernel_s = tr.seconds_matching(lambda op: op == KERNEL)
    if kernel_s <= 0:
        return None
    return 100.0 * ctx["model"].state_least_s(
        ctx["config"], c, ctx["peaks"]) / kernel_s
