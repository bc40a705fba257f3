"""Share of the held experts' weights that a step had to read: held
experts that got at least one active token (``expert_hits_held``, counted
by the step itself over its expert layers) over held experts x expert
layers x steps (``expert_layer_steps``), from DecodeEngine.stats() over
the window. It sizes a grouped product that would skip the experts no
token chose. An engine without these counters reads nothing."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("expert_layer_steps"):
        return None
    return 100.0 * c["expert_hits_held"] / (
        ctx["model"].held_experts(ctx["config"]) * c["expert_layer_steps"])
