"""The latent (MLA) decode-attention kernel's share of its roofline: the
least time for its work (the latent row, [c_kv | k_rope], of every cached
token attended to, read once at the HBM peak, against the absorbed form's
FLOPs at the bf16 peak, the larger; the counts are the cell's model
file's) over the device time of the kernel's events, both of the traced
part. A program without the kernel (or a trace without its events) reads
nothing."""

#: ops/pallas_decode.py's ``paged_latent_attention`` as the trace reducer
#: shows it (benchmarks/lib/trace.short_name): matched exactly
KERNEL = "tpu_custom_call:paged_latent_attention"


def read(ctx):
    tr, c = ctx.get("trace"), ctx.get("traced_counters")
    if tr is None or not c or not c.get("cache_tokens_read"):
        return None
    kernel_s = tr.seconds_matching(lambda op: op == KERNEL)
    if kernel_s <= 0:
        return None
    least, _ = ctx["model"].paged_attn_least_s(
        ctx["config"], c, ctx["kv_itemsize"], ctx["peaks"])
    return 100.0 * least / kernel_s
