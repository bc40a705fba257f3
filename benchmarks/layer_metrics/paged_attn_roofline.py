"""The decode-attention kernel's share of its roofline: the least time
for the K/V bytes it must read (cache_tokens_read x bytes per token at the
HBM peak; its FLOPs at the bf16 peak are 200x less, so bytes bind) over
the device time of the kernel's events, both of the traced part."""
from benchmarks.lib.names import is_paged_attn_kernel


def read(ctx):
    tr, c = ctx.get("trace"), ctx.get("traced_counters")
    if tr is None or not c or not c.get("cache_tokens_read"):
        return None
    kernel_s = tr.seconds_matching(is_paged_attn_kernel)
    if kernel_s <= 0:
        return None
    least, _ = ctx["model"].paged_attn_least_s(
        ctx["config"], c, ctx["kv_itemsize"], ctx["peaks"])
    return 100.0 * least / kernel_s
