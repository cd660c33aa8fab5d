"""The readers of the program's named device programs and chunk loop
(``loop_idle_ms``, ``epilogue_ms``) on a synthetic trace with known
values, on the recorded trace of a program that did not name its programs
yet (``ot32_two_calls``), and on one recorded through the harness on a TPU
v5e with the program's names and spans (``ot32_two_calls_spans``: two
single-pair WhiteNoise 32 x 32 OT calls at eps 0.1, one chunk each)."""
import gzip
import json

import numpy as np
import pytest

import tracefile
from drive import BENCH, Run, Unit, reader

DATA = BENCH / "tests" / "data"
MS = 1e6          # ns


def _run(trace, windows, chunk_s=()):
    """A run of one call per window; ``chunk_s`` holds each call's chunk
    times (s)."""
    units = [Unit(wall_s=0.0, lanes=1, m=1, n=1, ok=1,
                  chunks=[{"chunk_s": c} for c in cs]) for cs in chunk_s]
    return Run(setup_s=0.0, window_s=0.0, units=units, peak_bytes=[],
               peak={}, work=None, trace=trace,
               windows=np.asarray(windows, float))


def _recorded(name):
    tr = tracefile.load(str(DATA / f"{name}.xplane.pb.gz"))
    meta = json.loads((DATA / f"{name}.json").read_text())
    # one chunk a call in both recordings
    return tr, meta, _run(tr, [(lo, hi) for n, lo, hi in tr.marks
                               if n == "bench.unit"],
                          [[c] for c in meta["chunk_s"]])


def _device(index, modules, ops):
    names = [n for n, _, _ in modules]
    return tracefile.Device(
        index=index, modules=np.asarray([(a, b) for _, a, b in modules]),
        module_names=names, ops=np.asarray(ops, float).reshape(-1, 2),
        op_names=[f"op.{i}" for i in range(len(ops))])


# the host spans of one call's two chunks (ms from its start): launch of
# the chunk program through the fetch of its converged mask
CHUNKS = ((11, 23), (28, 43))


def _call(t0, chunk_gap):
    """One call from ``t0`` (ms): prologue, two chunks each with its
    converged-mask check, the epilogue. The first chunk's ops leave
    ``chunk_gap`` ms idle inside it; 1 ms passes between each chunk and
    its check, 8 ms between the first check and the second chunk."""
    mods = [("jit_ot_prologue(11)", 0, 10), ("jit_ot_chunk(22)", 12, 20),
            ("jit_ot_conv(33)", 21, 22), ("jit_ot_chunk(22)", 30, 40),
            ("jit_ot_conv(33)", 41, 42), ("jit_ot_epilogue(44)", 50, 60),
            ("jit__feasibility_margin(55)", 62, 65)]
    ops = [(a, b) for n, a, b in mods if n != "jit_ot_chunk(22)"]
    ops += [(12, 16), (16 + chunk_gap, 20), (30, 40)]
    return ([(n, (t0 + a) * MS, (t0 + b) * MS) for n, a, b in mods],
            [((t0 + a) * MS, (t0 + b) * MS) for a, b in ops])


@pytest.mark.parametrize("chips", [1, 2])
def test_readers_on_a_synthetic_trace(chips):
    devices = []
    for i in range(chips):
        m1, o1 = _call(0.0, chunk_gap=1.0 + i)
        m2, o2 = _call(100.0, chunk_gap=1.0 + i)
        devices.append(_device(i, m1 + m2, o1 + o2))
    marks = [(tracefile.CHUNK_MARK, (t0 + b) * MS, (t0 + b) * MS)
             for t0 in (0.0, 100.0) for _, b in CHUNKS]
    tr = tracefile.Trace(devices=devices, launches=np.zeros(0), marks=marks,
                         shift_ns=0.0)
    chunk_s = [[1e-3 * (b - a) for a, b in CHUNKS]] * 2
    run = _run(tr, [[0, 90 * MS], [100 * MS, 190 * MS]], chunk_s)
    # loop 11-43 ms: 32 ms, of which 4 + 4 - gap + 1 + 10 + 1 busy; the
    # prologue before the first chunk and the epilogue after the last
    # are outside it
    gap = np.mean([1.0 + i for i in range(chips)])
    assert reader("loop_idle_ms.solve").read(run) == pytest.approx(
        32 - (20 - gap))
    assert reader("epilogue_ms.batch").read(run) == pytest.approx(10.0)
    # a window that holds no chunk adds nothing to the sum but counts as
    # a call
    run3 = _run(tr, [[0, 90 * MS], [100 * MS, 190 * MS],
                     [300 * MS, 400 * MS]], chunk_s + [[]])
    assert reader("loop_idle_ms.solve").read(run3) == pytest.approx(
        2 * (32 - (20 - gap)) / 3)
    assert reader("epilogue_ms.solve").read(run3) == pytest.approx(20 / 3)
    # without chunk events there is no loop to bound
    assert reader("loop_idle_ms.solve").read(
        _run(tr, run.windows)) is None


def test_readers_find_nothing_without_program_names():
    tr, _, run = _recorded("ot32_two_calls")
    assert len(run.windows) == 2
    assert reader("epilogue_ms.solve").read(run) is None
    for metric in ("loop_idle_ms.solve", "epilogue_ms.solve"):
        assert reader(metric).read(_run(None, run.windows)) is None


@pytest.mark.parametrize("name", ["ot32_two_calls", "ot32_two_calls_spans"])
def test_loop_idle_is_the_idle_inside_the_chunk_spans(name):
    """The loop is bounded by the benchmark's chunk spans, which a program
    that names nothing emits too: with one chunk a call, each call's loop
    is its chunk."""
    tr, meta, run = _recorded(name)
    spans = tracefile.chunk_spans(tr, meta["chunk_s"])
    idle = [(b - a) - np.mean([tracefile.busy_ns(d, np.asarray([[a, b]]))
                               for d in tr.devices]) for a, b in spans]
    value = reader("loop_idle_ms.solve").read(run)
    assert value == pytest.approx(1e-6 * np.mean(idle))
    assert 0 < value < 1e3 * np.mean(meta["chunk_s"])


@pytest.fixture(scope="module")
def named():
    return (DATA / "ot32_two_calls_spans.xplane.pb.gz",
            *_recorded("ot32_two_calls_spans"))


def test_readers_on_a_recorded_trace_with_named_programs(named):
    _, tr, meta, run = named
    d0 = tr.devices[0]
    programs = {tracefile._FINGERPRINT.sub("", n) for n in d0.module_names}
    assert {f"jit_ot_{s}" for s in ("prologue", "init", "chunk", "conv",
                                    "epilogue")} <= programs
    assert "jit__lambda" not in programs
    # the launch-order guess of the existing readers finds the same two
    assert {tracefile._FINGERPRINT.sub("", n)
            for n in tracefile.phase_loop_programs(tr)} == {
                "jit_ot_chunk", "jit_ot_conv"}
    assert len(tracefile.chunk_spans(tr, meta["chunk_s"])) == 2
    epilogue = reader("epilogue_ms.solve").read(run)
    names = {n for n in d0.module_names if n.startswith("jit_ot_epilogue(")}
    assert epilogue == pytest.approx(
        1e-6 * tracefile.module_ns(tr, names, run.windows) / 2)
    assert 0 < epilogue < 1e-6 * np.sum(run.windows[:, 1]
                                        - run.windows[:, 0]) / 2


def test_program_spans_on_the_recorded_host_plane(named):
    from jax.profiler import ProfileData

    path, tr, meta, run = named
    pd = ProfileData.from_serialized_xspace(gzip.decompress(
        path.read_bytes()))
    spans = sorted((e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for p in pd.planes if p.name == "/host:CPU"
                   for ln in p.lines for e in ln.events
                   if e.name.startswith("repro."))
    chunks = [(a, b) for n, a, b in spans if n == "repro.solve.chunk"]
    prepares = [n for n, _, _ in spans if n == "repro.solve.prepare"]
    # one chunk region per chunk event, two prepare regions per call (the
    # front door's solver routing, the compacting driver's prep), all
    # inside the calls
    assert len(chunks) == len(meta["chunk_s"]) == 2
    assert len(prepares) == 2 * len(run.windows)
    assert all(any(lo <= a and b <= hi for lo, hi in run.windows)
               for _, a, b in spans)
    # the region holds the interval the driver times, and ends before the
    # harness marks the chunk event
    marks = [lo for n, lo, _ in tr.marks if n == tracefile.CHUNK_MARK]
    for (a, b), chunk_s, mark in zip(sorted(chunks), meta["chunk_s"],
                                     marks):
        assert b - a >= chunk_s * 1e9
        assert b <= mark
    # so the chunk spans loop_idle_ms reads (mark minus chunk_s) differ
    # from the regions by less than the region's own slack and the time
    # from its end to the mark, and so does the idle inside them
    slack = max((b - a - chunk_s * 1e9) + (mark - b) for (a, b), chunk_s,
                mark in zip(sorted(chunks), meta["chunk_s"], marks))
    region_idle = np.mean([(b - a) - tracefile.busy_ns(
        tr.devices[0], np.asarray([[a, b]])) for a, b in chunks])
    assert reader("loop_idle_ms.solve").read(run) == pytest.approx(
        1e-6 * region_idle, abs=1e-6 * slack)
    # the existing readers never see the program's spans
    assert not any(n.startswith("repro.") for n, _, _ in tr.marks)
