"""Device time of the update kernel's events, summed over chips, per
request the harness saw served in the traced window (device trace)."""


def read(ctx):
    if ctx.trace is None or not ctx.traced_done or ctx.trace["kernel_s"] <= 0:
        return None
    return ctx.trace["kernel_s"] / ctx.traced_done * 1e6
