"""Device idle inside the chunk loop per solve call: from the start of a
call's first chunk to the end of its last, the time in which no operation
ran on a chip, mean over the chips the cell uses. A chunk is the host
interval the driver times as ``chunk_s``, from the chunk program's launch
through the converged-mask fetch (``tracefile.chunk_spans``; the program's
``repro.solve.chunk`` region holds it). These gaps are the host's round
trip per chunk: the launch, the mask fetch and any re-bucketing."""
import numpy as np

import tracefile


def read(run):
    tr = run.trace
    chunk_s = [c["chunk_s"] for u in run.units for c in u.chunks]
    if tr is None or not tr.devices or not len(run.windows) or not chunk_s:
        return None
    spans = tracefile.chunk_spans(tr, chunk_s)
    idle, found = 0.0, False
    for lo, hi in run.windows:
        inside = spans[(spans[:, 0] >= lo) & (spans[:, 1] <= hi)]
        if not len(inside):
            continue
        found = True
        loop = np.asarray([[inside[:, 0].min(), inside[:, 1].max()]])
        width = float(loop[0, 1] - loop[0, 0])
        idle += sum(width - tracefile.busy_ns(d, loop)
                    for d in tr.devices) / len(tr.devices)
    return 1e-6 * idle / len(run.windows) if found else None
