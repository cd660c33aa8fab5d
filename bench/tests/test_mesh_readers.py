"""The readers of the compacting drivers' re-bucket and per-chip counters
(``rebucket_ms``, ``chip_skew_pct``) on hand-built runs: a known value,
and None where the program emits no such counter."""
import pytest

from drive import Run, Unit, reader


def _run(*calls):
    """A run of one call per argument, each a list of chunk events."""
    units = [Unit(wall_s=1.0, lanes=8, m=4, n=4, ok=8, chunks=list(c))
             for c in calls]
    return Run(setup_s=0.0, window_s=1.0, units=units, peak_bytes=[],
               peak={}, work=None)


def _chunk(bucket, rebucket_s=None, chip_phases=None):
    e = {"bucket": bucket, "live": bucket, "chunk_s": 0.01,
         "phases": max(chip_phases or [8]), "compiled": 0}
    if rebucket_s is not None:
        e["rebucket_s"] = rebucket_s
    if chip_phases is not None:
        e["devices"] = len(chip_phases)
        e["chip_phases"] = chip_phases
    return e


@pytest.mark.parametrize("split", ["mesh", "batch"])
def test_rebucket_ms_sums_a_call_and_averages_calls(split):
    run = _run([_chunk(32, 0.0), _chunk(32, 0.0), _chunk(16, 0.004),
                _chunk(8, 0.002)],
               [_chunk(32, 0.0), _chunk(16, 0.006)])
    # 6 ms and 6 ms
    assert reader(f"rebucket_ms.{split}").read(run) == pytest.approx(6.0)
    # a call with no re-bucket counts as 0 ms
    run = _run([_chunk(32, 0.0), _chunk(16, 0.004)], [_chunk(32, 0.0)])
    assert reader(f"rebucket_ms.{split}").read(run) == pytest.approx(2.0)


def test_rebucket_ms_is_none_without_the_counter():
    assert reader("rebucket_ms.mesh").read(
        _run([_chunk(32), _chunk(16)])) is None
    assert reader("rebucket_ms.mesh").read(_run()) is None
    assert reader("rebucket_ms.mesh").read(_run([])) is None


def test_chip_skew_counts_the_slots_chips_wait_for_the_slowest():
    run = _run([_chunk(32, 0.0, [8, 8, 8, 8]),       # no wait
                _chunk(16, 0.001, [8, 6, 4, 2]),     # 12 of 32 waited
                _chunk(2, 0.001, [5])],              # one device: left out
               [_chunk(32, 0.0, [4, 0, 4, 0])])      # 8 of 16 waited
    used = 32 + 20 + 8
    slots = 32 + 32 + 16
    assert reader("chip_skew_pct.mesh").read(run) == pytest.approx(
        100.0 * (1 - used / slots))


def test_chip_skew_is_none_without_sharded_chunks():
    # the parent's events, and the one-device driver's
    assert reader("chip_skew_pct.mesh").read(
        _run([_chunk(32, 0.0), _chunk(16, 0.001)])) is None
    assert reader("chip_skew_pct.mesh").read(
        _run([_chunk(2, 0.0, [5]), _chunk(1, 0.001, [3])])) is None
    # sharded chunks that ran no phase on any chip
    assert reader("chip_skew_pct.mesh").read(
        _run([_chunk(4, 0.0, [0, 0, 0, 0])])) is None
