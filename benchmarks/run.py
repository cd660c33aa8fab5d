"""One function per paper table/figure. Prints ``name,us_per_call,derived``
CSV. ``python -m benchmarks.run [--full]`` (full = paper-scale grids).

``--diff`` compares a fresh run of the JSON-emitting families (batched,
sharded, solution, faults, serve, kernels, portfolio) against the committed
``BENCH_*.json`` instead of overwriting them, flags any >20%
instances/sec regression, and exits nonzero if one is found — the perf
gate for driver AND kernel refactors.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

DIFF_THRESHOLD = 0.2     # flag >20% instances/sec regressions

# (name, old, new, ratio, tag) rows accumulated across diff_records calls,
# rendered as a markdown table into GITHUB_STEP_SUMMARY when CI sets it
_DIFF_ROWS: list = []


def diff_records(fresh: list, committed_path: str,
                 threshold: float = DIFF_THRESHOLD) -> list:
    """Compare ``instances_per_s`` between fresh records and the committed
    baseline (matched by record name). Prints one line per comparable
    record; returns the names that regressed by more than ``threshold``."""
    if not os.path.exists(committed_path):
        print(f"# no committed {committed_path}; nothing to diff",
              file=sys.stderr, flush=True)
        return []
    with open(committed_path) as f:
        base = {r["name"]: r for r in json.load(f)["records"]}
    regressions = []
    print(f"# --- diff vs {committed_path} "
          f"(flagging >{threshold:.0%} instances/sec regressions) ---",
          file=sys.stderr, flush=True)
    for r in fresh:
        b = base.get(r["name"])
        if (b is None or "instances_per_s" not in r
                or "instances_per_s" not in b):
            continue
        new, old = float(r["instances_per_s"]), float(b["instances_per_s"])
        ratio = new / old if old > 0 else float("inf")
        regressed = ratio < 1.0 - threshold
        tag = "REGRESSION" if regressed else "ok"
        print(f"# {r['name']}: {old:.1f} -> {new:.1f} inst/s "
              f"({ratio - 1.0:+.1%}) {tag}", file=sys.stderr, flush=True)
        _DIFF_ROWS.append((r["name"], old, new, ratio, tag))
        if regressed:
            regressions.append(r["name"])
    return regressions


def write_step_summary(regressions: list,
                       path: str = "") -> None:
    """Render the accumulated diff rows as a markdown table into the CI
    step summary (``GITHUB_STEP_SUMMARY``); no-op outside CI."""
    path = path or os.environ.get("GITHUB_STEP_SUMMARY", "")
    if not path or not _DIFF_ROWS:
        return
    lines = ["## Benchmark diff vs committed baselines", "",
             f"Threshold: >{DIFF_THRESHOLD:.0%} instances/sec regression "
             f"fails the job.", "",
             "| bench | baseline inst/s | fresh inst/s | delta | status |",
             "|---|---:|---:|---:|---|"]
    for name, old, new, ratio, tag in _DIFF_ROWS:
        status = ":x: REGRESSION" if tag == "REGRESSION" else ":white_check_mark: ok"
        lines.append(f"| `{name}` | {old:.1f} | {new:.1f} | "
                     f"{ratio - 1.0:+.1%} | {status} |")
    lines.append("")
    lines.append(f"**{len(regressions)} regression(s)**" if regressions
                 else "**diff clean**")
    with open(path, "a") as f:
        f.write("\n".join(lines) + "\n")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale grids (slow on CPU)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: synthetic,mnist,"
                         "ot,batched,sharded,solution,faults,"
                         "serve,kernels,portfolio")
    ap.add_argument("--diff", action="store_true",
                    help="compare fresh batched/sharded results against "
                         "the committed BENCH_*.json (no overwrite); exit "
                         "1 on a >20%% instances/sec regression")
    args = ap.parse_args()

    from . import bench_synthetic, bench_mnist, \
        bench_ot, bench_batched, bench_sharded, \
        bench_solution, bench_faults, bench_serve, bench_kernels, \
        bench_portfolio

    benches = {
        "synthetic": bench_synthetic.run,   # paper Fig. 1
        "mnist": bench_mnist.run,           # paper Fig. 2
        "ot": bench_ot.run,                 # Section 4 clustered solver
        "batched": bench_batched.run,       # batched serving subsystem
        "sharded": bench_sharded.run,       # mesh-distributed dispatch
        "solution": bench_solution.run,     # typed result surface fetch
        "faults": bench_faults.run,         # admission gate + recovery
        "serve": bench_serve.run,           # saturation + obs overhead
        "kernels": bench_kernels.run,       # fused vs stepped phase loop
        #   (also carries the Section 3.2 phase-bound rows that lived in
        #   the retired bench_phases family)
        "portfolio": bench_portfolio.run,   # solver crossover sweep
    }
    if args.diff and args.only is None:
        # diff mode only makes sense for the JSON-emitting families
        args.only = "batched,sharded,solution,faults,serve,kernels,portfolio"
    only = set(args.only.split(",")) if args.only else set(benches)
    if args.diff and not ({"batched", "sharded", "solution",
                           "faults", "serve", "kernels",
                           "portfolio"} & only):
        ap.error("--diff compares the JSON-emitting families; include "
                 "batched, sharded, solution, faults, serve, kernels "
                 "and/or portfolio in --only")
    regressions: list = []
    print("name,us_per_call,derived")
    for name, fn in benches.items():
        if name not in only:
            continue
        print(f"# --- {name} ---", file=sys.stderr, flush=True)
        fn(full=args.full)
        if name == "batched":
            # machine-readable perf trajectory: instances/sec, the
            # lockstep-waste metric (phases executed vs needed), and the
            # compaction occupancy curve, for future PRs to diff against
            if args.diff:
                regressions += diff_records(bench_batched.RECORDS,
                                            "BENCH_batched.json")
            else:
                bench_batched.write_json("BENCH_batched.json")
        if name == "sharded":
            # instances/sec vs device count + occupancy + mesh topology
            # (the bench re-execs itself under a forced 8-device CPU)
            if args.diff:
                regressions += diff_records(bench_sharded.RECORDS,
                                            "BENCH_sharded.json")
            else:
                bench_sharded.write_json("BENCH_sharded.json")
        if name == "solution":
            # host-fetch bytes + wall time per declared artifact set
            # (cost-only vs sparse vs dense plans)
            if args.diff:
                regressions += diff_records(bench_solution.RECORDS,
                                            "BENCH_solution.json")
            else:
                bench_solution.write_json("BENCH_solution.json")
        if name == "faults":
            # healthy-path admission overhead (<5% budget asserted) +
            # poisoned-bucket recovery latency
            if args.diff:
                regressions += diff_records(bench_faults.RECORDS,
                                            "BENCH_faults.json")
            else:
                bench_faults.write_json("BENCH_faults.json")
        if name == "serve":
            # p50/p99 latency + throughput vs offered load through the
            # async scheduler, and the asserted <2% no-sink obs budget
            if args.diff:
                regressions += diff_records(bench_serve.RECORDS,
                                            "BENCH_serve.json")
            else:
                bench_serve.write_json("BENCH_serve.json")
        if name == "kernels":
            # us/phase + phases/sec per kernel per backend, fused vs
            # unfused phase loop (parity-asserted per row)
            if args.diff:
                regressions += diff_records(bench_kernels.RECORDS,
                                            "BENCH_kernels.json")
            else:
                bench_kernels.write_json("BENCH_kernels.json")
        if name == "portfolio":
            # per-instance seconds per (solver, n, eps) across the
            # paper's crossover sweep: pushrelabel vs sinkhorn vs hybrid
            if args.diff:
                regressions += diff_records(bench_portfolio.RECORDS,
                                            "BENCH_portfolio.json")
            else:
                bench_portfolio.write_json("BENCH_portfolio.json")
    if args.diff:
        write_step_summary(regressions)
        if regressions:
            print(f"# PERF REGRESSIONS ({len(regressions)}): "
                  + ", ".join(regressions), file=sys.stderr, flush=True)
            sys.exit(1)
        print("# diff clean: no instances/sec regression beyond "
              f"{DIFF_THRESHOLD:.0%}", file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
