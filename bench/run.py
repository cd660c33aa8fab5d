#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json`` on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``bench/configs/<config>.json``, read
through ``BENCHMARK.json``) and a traffic mix
(``bench/traffic/<traffic>.json``). The configuration's ``"instance"``
names its generator (``bench/instances/<instance>.py``) and its
``"problem"`` the solve call (``bench/problems/<problem>.py``), the plain
reference (``bench/reference/<problem>.py``) and the phase loop's bytes
(``bench/work/<problem>.py``); metric ``<name>`` is read by
``bench/metrics/<name up to the first dot>.py``; the chip's peaks come
from ``bench/peaks.json``. A new configuration, cell, mix or metric is a
new file and a new entry, never an edit.

``--trace 0`` measures the cell's end-to-end metrics over a window of
``--seconds``; ``--trace 1`` traces the mix's ``trace_units`` calls with
the profiler and reports the per-layer metrics. Both check the answers
against the plain reference (``bench/reference``) and print each compared
number beside its limit as the last lines of standard error and under
``"checks"``, the last key of the result. The result is the last line of
standard output. Without a TPU, with fewer chips than the cell asks for,
or on a chip missing from the peak table, the run exits 2 and prints no
result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        return _fail(f"no cell {args.workload!r} in BENCHMARK.json")
    cell = cells[args.workload]
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((ROOT / cfg["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    chips = int(cell["chips"])

    sys.path.insert(0, str(ROOT / "src"))
    import jax

    import repro.core.api  # noqa: F401  (the system under test)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return _fail(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        return _fail(f"cell {cell['name']} needs {chips} chips, JAX found "
                     f"{len(devices)}")
    peaks = json.loads((BENCH / "peaks.json").read_text())
    kind = devices[0].device_kind
    if kind not in peaks:
        return _fail(f"device kind {kind!r} is not in bench/peaks.json")

    import drive

    out = drive.run(config, traffic, chips=chips, seed=args.seed,
                    seconds=args.seconds, trace=bool(args.trace),
                    devices=devices, peak=peaks[kind], t0=T0)
    rec = out.pop("run")
    metrics = {}
    for m in bench["per_layer" if args.trace else "end_to_end"]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        value = drive.reader(m["name"]).read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if args.trace:
        device["busy_s"], device["window_s"] = out["busy_s"], out["window_s"]
        result["breakdown"] = out["breakdown"]
    result["checks"] = out["checks"]
    for k, v in out["info"].items():
        print(f"info {k} {v!r}", file=sys.stderr)
    for k, c in out["checks"].items():
        verdict = ("ok" if c["value"] is not None and c["value"] <= c["limit"]
                   else "FAIL")
        print(f"check {k} {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
