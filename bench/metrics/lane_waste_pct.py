"""Share of the phase slots the compacting driver ran that no instance
needed: 1 - (sum of per-instance phases) / (lanes x phases per dispatch,
summed over dispatches), from ``CompactionStats``."""


def read(run):
    slots = sum(u.slot_phases for u in run.units)
    need = sum(u.phases_needed for u in run.units)
    return 100.0 * (1.0 - need / slots) if slots else None
