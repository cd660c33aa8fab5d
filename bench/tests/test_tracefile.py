"""The trace reduction on a trace recorded through the harness on a TPU v5e
(``data/ot32_two_calls``: two single-pair WhiteNoise 32 x 32 OT calls at
eps 0.1, with the chunk events' ``chunk_s`` beside it)."""
import json
from collections import Counter

import numpy as np
import pytest

import tracefile
from drive import BENCH

DATA = BENCH / "tests" / "data"


@pytest.fixture(scope="module")
def recorded():
    tr = tracefile.load(str(DATA / "ot32_two_calls.xplane.pb.gz"))
    meta = json.loads((DATA / "ot32_two_calls.json").read_text())
    units = [(lo, hi) for name, lo, hi in tr.marks if name == "bench.unit"]
    return tr, meta, units[0][0], units[-1][1]


def _win(lo, hi):
    return np.asarray([[lo, hi]])


def test_union_merges_and_clips():
    iv = np.asarray([[5, 7], [0, 2], [1, 3], [6, 9], [20, 30]], float)
    u = tracefile.union(iv, 1, 25)
    assert u.tolist() == [[1, 3], [5, 9], [20, 25]]
    assert tracefile.union(np.zeros((0, 2)), 0, 1).shape == (0, 2)


def test_layout_of_a_recorded_trace(recorded):
    tr, meta, lo, hi = recorded
    assert [d.index for d in tr.devices] == [0]
    d0 = tr.devices[0]
    # every launch executes once on the chip, no later than it was made
    assert len(tr.launches) == len(d0.modules) == 26
    assert np.all(d0.modules[:, 0] >= tr.launches)
    assert 0 < tr.shift_ns < 2e6
    assert sum(name == tracefile.CHUNK_MARK for name, _, _ in tr.marks) \
        == len(meta["chunk_s"]) == 2
    assert all(n.startswith("jit_") for n in d0.module_names)


def test_phase_loop_programs_are_chunk_and_check(recorded):
    tr, meta, lo, hi = recorded
    loop = tracefile.phase_loop_programs(tr)
    runs = Counter(tr.devices[0].module_names)
    # two programs, the chunk and its converged-mask check, each run once
    # per chunk event; both are jitted lambdas told apart by fingerprint
    assert len(loop) == 2
    assert all(name.startswith("jit__lambda(") for name in loop)
    assert all(runs[name] == len(meta["chunk_s"]) for name in loop)
    loop_ns = tracefile.module_ns(tr, loop, _win(lo, hi))
    mods = tr.devices[0].modules
    every = float(np.sum(mods[:, 1] - mods[:, 0]))
    assert 0 < loop_ns < every


def test_busy_and_idle_partition_the_window(recorded):
    tr, meta, lo, hi = recorded
    spans = tracefile.chunk_spans(tr, meta["chunk_s"])
    busy = tracefile.busy_ns(tr.devices[0], _win(lo, hi))
    idle = sum(s for _, s in tracefile.idle_gaps(tr, spans, _win(lo, hi))
               ) * 1e9
    assert 0 < busy < hi - lo
    assert busy + idle == pytest.approx(hi - lo, rel=1e-9)
    labels = {k for k, _ in tracefile.idle_gaps(tr, spans, _win(lo, hi))}
    assert labels <= {"phase loop: host sync between chunks",
                      "solve: before the phase loop",
                      "solve: after the phase loop", "solve: no chunk",
                      "fetch", "certificate", "unit", "between units"}


def test_device_ops_name_the_phase_loop(recorded):
    tr, meta, lo, hi = recorded
    loop = tracefile.phase_loop_programs(tr)
    ops = tracefile.device_ops(tr, loop, _win(lo, hi))
    assert 0 < len(ops) <= 10
    assert ops == sorted(ops, key=lambda kv: -kv[1])
    assert any(k.startswith("phase_loop/") for k, _ in ops)
    # self times: a while op is charged only for what its body ops leave
    assert sum(s for _, s in ops) * 1e9 <= tracefile.busy_ns(
        tr.devices[0], _win(lo, hi)) * (1 + 1e-9)


def test_split_windows_add_up(recorded):
    """The traced window is the calls' own spans: cut in two, every
    reduction adds up to the whole."""
    tr, meta, lo, hi = recorded
    mid = 0.5 * (lo + hi)
    halves = np.asarray([[lo, mid], [mid, hi]])
    d0, loop = tr.devices[0], tracefile.phase_loop_programs(tr)
    spans = tracefile.chunk_spans(tr, meta["chunk_s"])
    assert tracefile.busy_ns(d0, halves) == pytest.approx(
        tracefile.busy_ns(d0, _win(lo, hi)), rel=1e-12)
    assert tracefile.module_ns(tr, loop, halves) == pytest.approx(
        tracefile.module_ns(tr, loop, _win(lo, hi)), rel=1e-12)
    idle = sum(s for _, s in tracefile.idle_gaps(tr, spans, halves))
    assert idle == pytest.approx(sum(
        s for _, s in tracefile.idle_gaps(tr, spans, _win(lo, hi))),
        rel=1e-9)
    ops = dict(tracefile.device_ops(tr, loop, halves, top=1000))
    whole = dict(tracefile.device_ops(tr, loop, _win(lo, hi), top=1000))
    assert ops.keys() == whole.keys()
    assert all(ops[k] == pytest.approx(whole[k], rel=1e-9) for k in ops)


def test_chunk_spans_need_one_mark_per_event(recorded):
    tr, meta, _, _ = recorded
    with pytest.raises(ValueError):
        tracefile.chunk_spans(tr, meta["chunk_s"][:-1])
