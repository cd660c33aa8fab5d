"""Share of the phase loop's roofline: the least time the chip needs to
move the loop's bytes (``bench/work/<problem>.py``, summed over every
instance's exact rounds and phases) at its HBM bandwidth
(``bench/peaks.json``), over the device time of the phase-loop programs
in the trace (the k-phase chunk and its converged-mask check, summed over
chips). The loop is memory-bound, so bandwidth is its roofline."""


def read(run):
    if run.trace is None or not run.loop_programs:
        return None
    measured = run.loop_device_s()
    if measured <= 0:
        return None
    least = sum(run.work.loop_bytes(u.m, u.n, int(r), int(p))
                for u in run.units for r, p in zip(u.rounds, u.phases))
    return 100.0 * least / run.peak["hbm_bytes_per_s"] / measured
