"""Host time in ``bench/poll`` and ``bench/flush`` spans (batch close:
stack, pad, enqueue, per-request result slices; and pump) per request
served in the window."""


def read(ctx):
    t = ctx.spans.total.get("bench/poll", 0.0) + ctx.spans.total.get("bench/flush", 0.0)
    return t / ctx.served * 1e6 if ctx.served and t else None
