"""Propose/accept rounds of the phase loop per instance (the program's
exact ``Solution.rounds`` counter), mean over the traced instances."""


def read(run):
    lanes = [int(r) for u in run.units for r in u.rounds]
    return sum(lanes) / len(lanes) if lanes else None
