"""Requests whose results the harness saw ready within the window, per
second of window (host clock)."""


def read(ctx):
    return ctx.served / ctx.window_s
