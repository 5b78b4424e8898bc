"""Whole-fleet energy of the first replay (seed ``--seed``), which always
runs to its end, over the pods it placed."""


def read(ctx):
    return ctx.energy_j_per_pod
