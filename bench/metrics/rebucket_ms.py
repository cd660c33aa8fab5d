"""Host time of the compacting driver's re-buckets per solve call: the
``rebucket_s`` of a call's ``"chunk"`` events (the flush of the bucket
into the result buffer, the survivor gather and, on a mesh, the re-shard
or the collapse to one chip), summed per call, mean over the traced
calls (ms). None where the program emits no ``rebucket_s``."""


def read(run):
    calls = [[c["rebucket_s"] for c in u.chunks if "rebucket_s" in c]
             for u in run.units]
    if not calls or not all(calls):
        return None
    return 1e3 * sum(sum(c) for c in calls) / len(calls)
