"""What a step of the lane program costs on the device over a plain step
(ms), as the engine's own clock sees it: the mean timed lane step less the
mean timed plain step over the window (DecodeEngine.stats()'s
step_ns_lanes / steps_timed_lanes - step_ns_plain / steps_timed_plain). A
step is timed where it ran back to back with the one before: both syncs
waited for the device, and it was launched while its predecessor still
ran; its time is the distance between the two syncs' ends. Nothing to
read without a timed step of each program, or in a program without the
counters (the parent's)."""


def read(ctx):
    c = ctx.get("counters") or {}
    if not (c.get("steps_timed_plain") and c.get("steps_timed_lanes")):
        return None
    lanes = c["step_ns_lanes"] / c["steps_timed_lanes"]
    plain = c["step_ns_plain"] / c["steps_timed_plain"]
    return (lanes - plain) / 1e6
