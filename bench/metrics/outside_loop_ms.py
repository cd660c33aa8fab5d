"""Host time per solve call outside the chunk loop: the call's wall time
minus its chunks' ``chunk_s`` (front door, float prologue, epilogue,
certificate and fetches), mean over the traced calls."""


def read(run):
    if not run.units or not all(u.chunks for u in run.units):
        return None
    out = [u.wall_s - sum(c["chunk_s"] for c in u.chunks) for u in run.units]
    return 1e3 * sum(out) / len(out)
