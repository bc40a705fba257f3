"""Share of the engine's slots that held a request, over the window's
steps: active_slot_steps / (steps x slots), from DecodeEngine.stats()."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("steps"):
        return None
    return 100.0 * c["active_slot_steps"] / (c["steps"] * ctx["num_slots"])
