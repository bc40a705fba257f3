"""Share of the window in which the device ran dry before the engine's
next launch (%): DecodeEngine.stats()'s device_dry_ns over the window.
Each launch with a step in flight first checks, at the end of every phase
since that step was sent, whether it has finished (jax.Array.is_ready());
a launch that finds it finished is dry, and device_dry_ns adds the
device's idle time before it, estimated from the engine's own clock
(serving/engine.py DecodeEngine._dry). A program without the counter (the
parent's), or a window of no time, reads nothing."""


def read(ctx):
    c = ctx.get("counters") or {}
    if "device_dry_ns" not in c or not ctx.get("window_s"):
        return None
    return 100.0 * c["device_dry_ns"] / (ctx["window_s"] * 1e9)
