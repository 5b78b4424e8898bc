"""95th percentile of the wall time of every ``select_many`` call in the
window (sync, upload, scoring, readback and greedy commit), linear
interpolation between order statistics."""
import numpy as np


def read(ctx):
    if len(ctx.rounds) < 20:
        return None
    return 1000.0 * float(np.percentile([r.select_s for r in ctx.rounds], 95))
