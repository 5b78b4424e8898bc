"""Mean wall time of ``BatchScheduler.score_queue`` per scheduling round:
cache sync, fit mask, upload, dispatch and readback."""


def read(ctx):
    if not ctx.n_rounds:
        return None
    return 1000.0 * ctx.score_s / ctx.n_rounds
