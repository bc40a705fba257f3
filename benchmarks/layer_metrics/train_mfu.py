"""The whole training window's share of the chips' peak: (6 x the
blocks' weights + 6 d V for the tied head + 3 x causal attention forward)
per token x tokens per second of the window, over chips x peak bf16
FLOP/s. Recomputation is not counted."""


def read(ctx):
    rate = ctx["end_to_end"].get("train_tok_s")
    if not rate:
        return None
    per_tok = ctx["model"].train_flops_per_token(ctx["config"], ctx["seq_len"])
    return 100.0 * per_tok * rate / (ctx["chips"] * ctx["peaks"]["bf16_flops"])
