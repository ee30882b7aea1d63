"""Share of two traced whole steps in which no operation ran on the device:
1 - (union of the device's operation intervals) / (the traced span),
averaged over the chips."""


def read(ctx):
    t = ctx.trace
    if t is None or not t["devices"] or t["window_s"] <= 0:
        return None
    return 1.0 - t["busy_s"] / t["window_s"]
