"""``BatchScheduler.select_many`` calls in the window over the pods placed
in it: scheduling rounds and the autoscaler's probes for a node to wake
alike. Each probe scores one pod, so the wake pass's share of the calls
is what batching its probes into one call would remove."""


def read(ctx):
    if not ctx.placed:
        return None
    return ctx.n_rounds / ctx.placed
