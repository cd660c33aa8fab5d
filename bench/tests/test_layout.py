"""``BENCHMARK.json`` and the files the harness finds by its names."""
import json
import re

import pytest

import drive
import instances

ROOT, BENCH = drive.ROOT, drive.BENCH
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]
CELLS = {w["name"]: w for w in SPEC["workloads"]}
CONFIGS = {c["name"]: c for c in SPEC["configs"]}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_files(cell):
    """Everything a cell runs on is found by the names its configuration
    and traffic give."""
    w = CELLS[cell]
    config = json.loads((ROOT / CONFIGS[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    assert config["name"] == w["config"]
    adapter = drive.problem(config["problem"])
    assert callable(adapter.answer) and "cost" in adapter.WANT
    assert (BENCH / "work" / f"{config['problem']}.py").exists()
    assert traffic["mode"] in instances.family(config["instance"]).MODES
    # the limits are numbers this problem's reference gives
    ref = drive.reference(config["problem"])
    assert callable(ref.check)
    assert config["limits"] and set(config["limits"]) <= set(ref.NUMBERS)
    for name in (w["name"], w["config"], w["traffic"]):
        assert NAME.match(name)
    assert len(w["why"]) <= 200


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_metric_reader(metric):
    assert NAME.match(metric)
    assert callable(drive.reader(metric).read)
    m = next(x for x in METRICS if x["name"] == metric)
    assert set(m.get("workloads", CELLS)) <= set(CELLS)


def test_every_cell_reports_setup_and_layers():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in e2e[
        "setup_s"]
    for cell in CELLS:
        assert sum(cell in m.get("workloads", CELLS)
                   for m in SPEC["end_to_end"]) >= 2
        assert any(cell in m.get("workloads", CELLS)
                   for m in SPEC["per_layer"])
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(e2e[m["moves"]].get("workloads",
                                                              CELLS))


def test_peak_table_is_keyed_by_device_kind():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    for kind, row in peaks.items():
        assert row["hbm_bytes_per_s"] > 0 and row["source"]
