"""Device time of the float epilogue per solve call: the executions of
the programs named ``jit_<spec>_epilogue`` and ``jit_<spec>_mesh_epilogue``
(``compaction.spec_fns``, ``distributed._mesh_fns``) inside the traced
calls, mean over the chips the cell uses."""
import re

import tracefile

EPILOGUE = re.compile(r"^jit_\w+_epilogue(\(|$)")


def read(run):
    tr = run.trace
    if tr is None or not tr.devices or not len(run.windows):
        return None
    names = {n for d in tr.devices for n in d.module_names
             if EPILOGUE.match(n)}
    if not names:
        return None
    ns = tracefile.module_ns(tr, names, run.windows) / len(tr.devices)
    return 1e-6 * ns / len(run.windows)
