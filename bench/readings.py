#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell at
its own size, in one process:

    python3 bench/readings.py --workload <cell> [--control-calls 3]

Solves every instance of the cell once through the timed path's solve
call (a single-instance cell's whole pool; a batch cell's one batch) and
checks each answer against the plain reference; then switches the policy
to the control (the program's unguaranteed path) and does the same for
``--control-calls`` calls. Prints one JSON line per call and, last, per
compared number the lower reading (largest over the program's calls) and
the upper reading (smallest over the control's calls). The benchmark's
own runs never run the control.
"""
import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control-calls", type=int, default=3)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((ROOT / cfg["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    import drive
    from repro.launch.platform import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    chips = int(cell["chips"])
    c = drive.Cell(config, traffic, chips, args.seed)
    readings = {"program": [], "control": []}
    for side, calls in (("program", len(c.pool.items)),
                        ("control", args.control_calls)):
        c.policy = drive.policy(chips, control=side == "control",
                                options=traffic.get("policy"))
        for k in range(calls):
            i = k % len(c.pool.items)
            t = time.perf_counter()
            sb, u = c.unit(i)
            pairs = c.answers(sb, i)
            del sb
            nums = drive.check_pairs(c.problem, c.eps, pairs)
            readings[side].append(nums)
            print(json.dumps({"side": side, "item": i, "wall_s": u.wall_s,
                              "certified": u.ok, "lanes": u.lanes,
                              "check_s": time.perf_counter() - t, **nums}),
                  flush=True)
    summary = {}
    for key in config["limits"]:
        summary[key] = {
            "lower": max(r[key] for r in readings["program"]),
            "upper": min(r[key] for r in readings["control"]),
            "limit": config["limits"][key]}
    print(json.dumps({"workload": args.workload, "readings": summary}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
