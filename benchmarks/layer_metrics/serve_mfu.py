"""The whole serving window's share of the chip's peak: model FLOPs of
every token fed (prompt and output) per second, over chips x peak bf16
FLOP/s. Tokens fed and their cache lengths are the engine's exact counters
over the whole window; the seconds are the host's."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("active_slot_steps"):
        return None
    flops = ctx["model"].serve_flops(ctx["config"], c)
    return 100.0 * flops / ctx["window_s"] / (
        ctx["chips"] * ctx["peaks"]["bf16_flops"])
