"""Host time in ``bench/submit`` spans (``ContinuousBatcher.submit``:
``make_request`` and admission) per request submitted in the window."""


def read(ctx):
    n = ctx.spans.count.get("bench/submit", 0)
    return ctx.spans.total["bench/submit"] / n * 1e6 if n else None
