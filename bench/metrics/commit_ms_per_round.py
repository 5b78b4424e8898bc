"""Mean wall time per scheduling round of ``select_many`` outside
``score_queue``: the greedy commit of the scores."""


def read(ctx):
    if not ctx.n_rounds:
        return None
    return 1000.0 * (ctx.select_s - ctx.score_s) / ctx.n_rounds
