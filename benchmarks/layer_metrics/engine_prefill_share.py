"""Share of the tokens fed in the window that were prompt tokens:
prefill_tokens / (prefill_tokens + tokens_out), from DecodeEngine.stats()."""


def read(ctx):
    c = ctx["counters"]
    fed = c.get("prefill_tokens", 0) + c.get("tokens_out", 0)
    return 100.0 * c["prefill_tokens"] / fed if fed else None
