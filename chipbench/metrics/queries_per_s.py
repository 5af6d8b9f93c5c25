"""Queries answered per second: every query of the window, over the whole
window (first send to last answer on the host), one closed-loop client."""


def read(ctx):
    if not ctx.queries or ctx.window_s <= 0:
        return None
    return len(ctx.queries) / ctx.window_s
