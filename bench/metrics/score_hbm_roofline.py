"""Share of the HBM roofline reached by the scoring rounds: the least
bytes the window's rounds had to move (``roofline.score_round_bytes``
from each round's shapes) over the device busy time inside the
harness's ``score_queue`` spans times the chip's peak HBM bandwidth."""
from roofline import peak, score_round_bytes


def read(ctx):
    tr = ctx.trace
    if tr is None or tr["busy_in_score_s"] <= 0 or not ctx.rounds:
        return None
    moved = sum(score_round_bytes(r.p, r.k, ctx.n_nodes, ctx.n_criteria)
                for r in ctx.rounds if r.scored)
    bandwidth = peak(ctx.device_kind, "hbm_bytes_per_s")
    return 100.0 * moved / (tr["busy_in_score_s"] * bandwidth)
