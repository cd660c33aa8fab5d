"""k-phase chunk dispatches, each ending in one converged-mask host sync,
per instance: the driver's ``"chunk"`` events over the instances of the
traced calls."""


def read(run):
    chunks = sum(len(u.chunks) for u in run.units)
    lanes = sum(u.lanes for u in run.units)
    return chunks / lanes if chunks else None
