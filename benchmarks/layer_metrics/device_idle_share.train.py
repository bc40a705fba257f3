"""Share of the traced window in which no operation ran on the device:
1 - union of the device's operation intervals over the window."""


def read(ctx):
    tr = ctx.get("trace")
    return None if tr is None else 100.0 * tr.idle_share()
