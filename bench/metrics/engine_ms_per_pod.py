"""Host time outside the scheduler per pod placed: the window's wall time
less the time inside ``select_many``, over the pods placed in it."""


def read(ctx):
    if not ctx.placed:
        return None
    return 1000.0 * (ctx.window_s - ctx.select_s) / ctx.placed
