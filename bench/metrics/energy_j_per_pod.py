"""Fleet energy of the first replay (seed ``--seed``), which always runs
to its end, over the pods it placed: the plain reference's
``reference_policies.Ledger.fleet_energy_j`` from the program's
placements, evictions and wakes."""


def read(ctx):
    return ctx.energy_j_per_pod
