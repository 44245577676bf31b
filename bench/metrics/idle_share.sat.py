"""1 - (union of device-op intervals) / traced window, mean over the
cell's chips (device trace)."""


def read(ctx):
    if ctx.trace is None or ctx.trace["window_s"] <= 0:
        return None
    w = ctx.trace["window_s"]
    return sum(1.0 - b / w for b in ctx.trace["busy_s"]) / len(ctx.trace["busy_s"])
