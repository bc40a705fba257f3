"""Share of the tokens the page trie matched at admission that a state
snapshot let the slot skip: snapshot_attach_tokens /
(snapshot_attach_tokens + snapshot_miss_tokens), from DecodeEngine.stats()
over the window. The rest were fed again for want of a snapshot as deep as
the pages. An engine without these counters, or a window in which the trie
matched nothing, reads nothing."""


def read(ctx):
    c = ctx.get("counters") or {}
    tokens = c.get("snapshot_attach_tokens", 0) + \
        c.get("snapshot_miss_tokens", 0)
    return 100.0 * c["snapshot_attach_tokens"] / tokens if tokens else None
