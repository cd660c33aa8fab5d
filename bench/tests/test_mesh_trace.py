"""The trace reduction on a four-chip trace, recorded through the harness
on a four-chip TPU v5e host (``data/wn8_pairs32_4chip``): a cut-down
two-call run of ``wn64.pairs32.eps0.02.4chip``, 32 WhiteNoise 8 x 8
pairs a call at eps 0.2, batch placement over the four chips. Each call
is one sharded chunk in which every lane converges, so no re-bucket
runs. Each call's chunk events are kept beside it."""
import json

import numpy as np
import pytest

import tracefile
from drive import BENCH, Run, Unit, reader

DATA = BENCH / "tests" / "data"
NAME = "wn8_pairs32_4chip"


@pytest.fixture(scope="module")
def recorded():
    tr = tracefile.load(str(DATA / f"{NAME}.xplane.pb.gz"))
    meta = json.loads((DATA / f"{NAME}.json").read_text())
    units = [Unit(wall_s=u["wall_s"], lanes=u["lanes"], m=u["m"], n=u["n"],
                  ok=u["lanes"], rounds=np.asarray(u["rounds"]),
                  phases=np.asarray(u["phases"]), chunks=u["chunks"])
             for u in meta["units"]]
    run = Run(setup_s=0.0, window_s=sum(u.wall_s for u in units),
              units=units, peak_bytes=[], peak={}, work=None, trace=tr,
              windows=np.asarray([(lo, hi) for n, lo, hi in tr.marks
                                  if n == "bench.unit"]))
    return tr, meta, run


def _programs(dev):
    return {tracefile._FINGERPRINT.sub("", n) for n in dev.module_names}


def test_four_devices_run_the_mesh_programs(recorded):
    tr, meta, run = recorded
    assert [d.index for d in tr.devices] == [0, 1, 2, 3]
    assert len(run.windows) == len(meta["units"]) == 2
    for d in tr.devices:
        assert {"jit_ot_mesh_prologue", "jit_ot_mesh_chunk",
                "jit_ot_mesh_conv", "jit_ot_mesh_epilogue"} <= _programs(d)
    chunks = [c for u in meta["units"] for c in u["chunks"]]
    assert sum(n == tracefile.CHUNK_MARK for n, _, _ in tr.marks) \
        == len(meta["chunk_s"]) == len(chunks) == 2
    assert all(c["devices"] == 4 and len(c["chip_phases"]) == 4
               for c in chunks)


def test_which_readers_read_on_the_mesh(recorded):
    """On this recording every launch pairs with a device-0 execution, so
    the launch-order guess finds the mesh's loop programs; on the
    full-size cell's trace it does not (PERF.md section 7). The
    chunk-span and program-name readers and the counter readers all read
    a value."""
    tr, meta, run = recorded
    assert len(tr.devices[0].modules) == len(tr.launches)
    loop = tracefile.phase_loop_programs(tr)
    assert {tracefile._FINGERPRINT.sub("", n) for n in loop} == {
        "jit_ot_mesh_chunk", "jit_ot_mesh_conv"}
    span = 1e-6 * float(np.sum(run.windows[:, 1] - run.windows[:, 0]))
    idle = reader("loop_idle_ms.mesh").read(run)
    epilogue = reader("epilogue_ms.mesh").read(run)
    assert 0 < idle < span / 2 and 0 < epilogue < span / 2
    # no re-bucket ran: the counter is there and reads zero
    assert reader("rebucket_ms.mesh").read(run) == 0.0
    # the chips' local max phases of the one sharded chunk of each call
    per_chip = [c["chip_phases"] for u in meta["units"] for c in u["chunks"]]
    used = sum(map(sum, per_chip))
    slots = sum(4 * max(p) for p in per_chip)
    assert reader("chip_skew_pct.mesh").read(run) == pytest.approx(
        100.0 * (1.0 - used / slots))
    assert 0 < used < slots
