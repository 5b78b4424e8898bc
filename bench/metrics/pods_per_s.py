"""Pods placed in the window over the window's wall time; a pod placed
again in the same replay counts once."""


def read(ctx):
    if ctx.window_s <= 0:
        return None
    return ctx.placed / ctx.window_s
