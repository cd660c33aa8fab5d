"""One run of one cell: set-up, the measured (or traced) window, and the
check of the answers the window produced against the plain reference.

Every timed unit is one call of ``repro.core.api.solve`` on pre-batched
device operands under ``DispatchPolicy(guaranteed=True)`` (batch
placement over the mesh on four chips). A unit ends when the call's cost
and duals are on the host and its certificate (``dual_feasible()``,
``additive_gap() <= additive_gap_bound()``) has been read.
"""
from __future__ import annotations

import glob
import importlib
import importlib.util
import shutil
import sys
import tempfile
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

import jax
import numpy as np

import instances
import tracefile

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_file(path: Path, name: str):
    """Import a module of the benchmark's by its file path."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """``bench/metrics/<name>.py`` for ``<name>`` or ``<name>.<split>``."""
    base = metric.split(".")[0]
    return load_file(BENCH / "metrics" / f"{base}.py", f"metric_{base}")


def problem(name: str):
    """``bench/problems/<name>.py``: the solve call of a problem."""
    return importlib.import_module(f"problems.{name}")


def reference(name: str):
    """``bench/reference/<name>.py``: the plain reference of a problem."""
    return importlib.import_module(f"reference.{name}")


@contextmanager
def _mark(name: str):
    with jax.profiler.TraceAnnotation("bench." + name):
        yield


class ChunkLog:
    """The ``obs`` the compacting drivers emit to: keeps every ``"chunk"``
    event and marks it on the profiler's host timeline (see tracefile)."""

    def __init__(self) -> None:
        self.chunks: List[dict] = []

    def event(self, name: str, **kw) -> None:
        if name == "chunk":
            with jax.profiler.TraceAnnotation(tracefile.CHUNK_MARK):
                pass
            self.chunks.append(kw)


class CompileCount:
    """Traces and XLA compiles JAX reports while it is armed."""

    def __init__(self) -> None:
        self.armed = False
        self.traces = self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event: str, *args, **kw) -> None:
        if not self.armed:
            return
        if event.endswith("jaxpr_trace_duration"):
            self.traces += 1
        elif event.endswith("backend_compile_duration"):
            self.compiles += 1


@dataclass
class Unit:
    """One solve call of the window."""
    wall_s: float
    lanes: int
    m: int
    n: int
    ok: int                                   # lanes the program certified
    rounds: np.ndarray = field(default_factory=lambda: np.zeros(0))
    phases: np.ndarray = field(default_factory=lambda: np.zeros(0))
    chunks: List[dict] = field(default_factory=list)
    slot_phases: int = 0
    phases_needed: int = 0


@dataclass
class Run:
    """What the metric readers read."""
    setup_s: float
    window_s: float
    units: List[Unit]
    peak_bytes: List[int]
    peak: dict
    work: Any
    trace: Optional[tracefile.Trace] = None
    loop_programs: Optional[set] = None
    windows: np.ndarray = field(default_factory=lambda: np.zeros((0, 2)))
    busy_s: float = 0.0
    trace_window_s: float = 0.0

    def loop_device_s(self) -> float:
        return 1e-9 * tracefile.module_ns(self.trace, self.loop_programs,
                                          self.windows)


def policy(chips: int, control: bool = False, options=None):
    """The solve call's policy: ``options`` (a traffic mix's ``"policy"``)
    and, on several chips, batch placement over the mesh. The control is
    the program's own unguaranteed path: it runs the solver at eps instead
    of eps / 3, and so breaks the duals' stated eps / 3 slack."""
    from repro.core.api import DispatchPolicy

    kw = dict(options or {})
    if chips > 1:
        from repro.launch.mesh import make_batch_mesh

        kw.update(mode="mesh", mesh=make_batch_mesh(chips),
                  placement="batch")
    return DispatchPolicy(guaranteed=not control, **kw)


class Cell:
    """A cell set up for runs: its instances on the device, the solve
    call's spec, policy and artifacts, and the seed's order."""

    def __init__(self, config: dict, traffic: dict, chips: int, seed: int,
                 control: bool = False) -> None:
        self.problem = config["problem"]
        self.adapter = problem(self.problem)
        self.spec = self.adapter.spec()
        self.want = tuple(self.adapter.WANT)
        self.eps = float(traffic["eps"])
        self.policy = policy(chips, control, traffic.get("policy"))
        self.rng = np.random.default_rng([seed, 0])
        self.sample_rng = np.random.default_rng([seed, 1])
        t = time.perf_counter()
        self.pool = instances.make_pool(config, traffic, self.rng)
        self.instances_s = time.perf_counter() - t
        self._order: List[int] = []

    def next_item(self) -> int:
        """Pool index of the next call: each pass over the pool in a fresh
        order drawn from the seed."""
        if not self._order:
            self._order = list(self.rng.permutation(len(self.pool.items)))
        return int(self._order.pop(0))

    def pass_open(self) -> bool:
        """Whether the current pass over the pool has calls left."""
        return bool(self._order)

    def unit(self, i: int, obs=None):
        """One timed solve call on pool item ``i``."""
        from repro.core.api import solve

        item = self.pool.items[i]
        with _mark("unit"):
            t = time.perf_counter()
            with _mark("solve"):
                sb = solve(self.spec, item, self.eps, self.policy,
                           want=self.want, obs=obs)
            with _mark("fetch"):
                sb.cost()
                sb.duals()
            with _mark("certificate"):
                ok = sb.dual_feasible() & (sb.additive_gap()
                                           <= sb.additive_gap_bound())
            wall = time.perf_counter() - t
        _, m, n = item["c"].shape
        return sb, Unit(wall_s=wall, lanes=len(sb), m=int(m), n=int(n),
                        ok=int(np.sum(ok)))

    def answers(self, sb, i: int) -> List[tuple]:
        """(host instance, answer) for every lane of call ``i``'s result."""
        if len(sb) != len(self.pool.host[i]):
            raise ValueError(f"call {i} answered {len(sb)} of "
                             f"{len(self.pool.host[i])} instances")
        y_b, y_a = sb.duals()
        cost = sb.cost()
        out = []
        for j, view in enumerate(sb):
            ans = {"cost": cost[j], "y_b": y_b[j], "y_a": y_a[j],
                   **self.adapter.answer(view)}
            out.append((self.pool.host[i][j], ans))
        return out


def check_pairs(problem_name: str, eps: float, pairs: List[tuple]
                ) -> Dict[str, float]:
    """The worst of each compared number over (instance, answer) pairs."""
    ref = reference(problem_name)
    worst: Dict[str, float] = {}
    for inst, ans in pairs:
        for k, v in ref.check(inst, ans, eps).items():
            worst[k] = max(worst.get(k, -np.inf), v)
    return worst


class Sample:
    """A uniform sample, drawn from the seed, of ``k`` of the window's
    calls (reservoir sampling). A call that enters it has its answers
    brought to the host at once and its result freed, so no checked
    answer stays on the device; ``fetch_s`` is the time that took, which
    the window leaves out."""

    def __init__(self, k: int, rng) -> None:
        self.k, self.rng = k, rng
        self.seen = 0
        self.kept: List[List[tuple]] = []
        self.failure: Optional[str] = None
        self.fetch_s = 0.0

    def offer(self, cell: "Cell", sb, i: int) -> None:
        self.seen += 1
        if len(self.kept) < self.k:
            slot = len(self.kept)
            self.kept.append([])
        else:
            slot = int(self.rng.integers(0, self.seen))
            if slot >= self.k:
                return
        t = time.perf_counter()
        with _mark("check.fetch"):
            try:
                self.kept[slot] = cell.answers(sb, i)
            except Exception as e:  # noqa: BLE001 - an answer never came
                self.failure = f"{type(e).__name__}: {e}"
        self.fetch_s += time.perf_counter() - t

    def pairs(self) -> List[tuple]:
        return [p for answers in self.kept for p in answers]


def _peaks(devices) -> List[int]:
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices]


def _trace_options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def _reduce_trace(tdir: str, chips: int, rec: Run, out: dict, log) -> None:
    """Read the traced window's profile into ``rec`` (for the per-layer
    readers) and ``out`` (busy time, window and breakdown), then delete
    it. The traced window is the traced calls' own spans: the fetch of
    the checked sample between them is left out, as in the timed
    window."""
    path = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
    tr = tracefile.load(path[0])
    shutil.rmtree(tdir, ignore_errors=True)
    tr.devices = [d for d in tr.devices if d.index < chips]
    win = np.asarray([(lo, hi) for name, lo, hi in tr.marks
                      if name == "bench.unit"]).reshape(-1, 2)
    spans = tracefile.chunk_spans(
        tr, [c["chunk_s"] for u in rec.units for c in u.chunks])
    loop = tracefile.phase_loop_programs(tr)
    busy = [tracefile.busy_ns(d, win) for d in tr.devices]
    rec.trace, rec.windows = tr, win
    rec.loop_programs = loop
    rec.trace_window_s = 1e-9 * float(np.sum(win[:, 1] - win[:, 0]))
    rec.busy_s = 1e-9 * float(np.mean(busy)) if busy else 0.0
    out["breakdown"] = {
        "device_ops": tracefile.device_ops(tr, loop or set(), win),
        "idle_gaps": tracefile.idle_gaps(tr, spans, win)}
    out["busy_s"], out["window_s"] = rec.busy_s, rec.trace_window_s
    comp = sum(c.get("compiled", 0) for u in rec.units for c in u.chunks)
    print(f"trace: {len(tr.devices)} chips, {len(spans)} chunks, "
          f"phase-loop programs {sorted(loop or ())}, {comp} chunk "
          f"programs compiled inside the window", file=log, flush=True)


def run(config: dict, traffic: dict, *, chips: int, seed: int,
        seconds: float, trace: bool, devices, peak: dict, t0: float,
        log=sys.stderr, control: bool = False) -> dict:
    """One run of a cell. Returns the result line's fields (without the
    device block) and the compared numbers with their limits."""
    from repro.launch.platform import use_compile_cache

    cache = use_compile_cache()
    # every program goes to the persistent cache, so that only the first
    # run of a cell in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    t_init = time.perf_counter()
    cell = Cell(config, traffic, chips, seed, control=control)
    t_warm = time.perf_counter()
    i = cell.next_item()
    sb, _ = cell.unit(i)
    cell.answers(sb, i)      # the checked sample's fetch programs, too
    del sb
    setup_s = time.perf_counter() - t0
    warm_s = time.perf_counter() - t_warm
    print(f"setup: {setup_s:.3f} s = start-up {t_init - t0:.3f} + instances "
          f"{cell.instances_s:.3f} + warm-up {warm_s:.3f} (cache {cache})",
          file=log, flush=True)

    count = CompileCount()
    sample = Sample(int(traffic["sample"]), cell.sample_rng)
    units: List[Unit] = []

    def keep(sb, i: int) -> None:
        count.armed = False
        sample.offer(cell, sb, i)
        count.armed = True

    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    count.armed = True
    if trace:
        obs = ChunkLog()
        with jax.profiler.trace(tdir, profiler_options=_trace_options()):
            for _ in range(int(traffic["trace_units"])):
                i = cell.next_item()
                n0 = len(obs.chunks)
                sb, u = cell.unit(i, obs=obs)
                u.chunks = obs.chunks[n0:]
                u.rounds, u.phases = sb.rounds(), sb.phases()
                st = sb.driver_stats
                u.slot_phases, u.phases_needed = st.slot_phases, \
                    st.phases_needed
                units.append(u)
                keep(sb, i)
                del sb
        window_s = sum(u.wall_s for u in units)
    else:
        # the window measures ``seconds`` and then finishes the pass over
        # the pool in progress (the first pass lacks the instance the
        # warm-up call took), so that runs of a cell do nearly the same
        # work; the sample's fetches are left out of it
        t_start = time.perf_counter()
        while (not units or cell.pass_open() or time.perf_counter()
               - t_start - sample.fetch_s < seconds):
            i = cell.next_item()
            sb, u = cell.unit(i)
            units.append(u)
            keep(sb, i)
            del sb
        window_s = time.perf_counter() - t_start - sample.fetch_s
    count.armed = False
    print(f"window: {len(units)} calls, {sum(u.lanes for u in units)} "
          f"instances in {window_s:.3f} s; {count.traces} traces and "
          f"{count.compiles} compiles inside it; {len(sample.kept)} calls "
          f"sampled, fetched in {sample.fetch_s:.3f} s (not counted)",
          file=log, flush=True)
    walls = np.asarray([u.wall_s for u in units])
    print(f"calls: min {walls.min():.4f} median {np.median(walls):.4f} "
          f"max {walls.max():.4f} s", file=log, flush=True)
    used = devices[:chips]
    out = {"attempted": sum(u.lanes for u in units),
           "failed": sum(u.lanes - u.ok for u in units),
           "memory_peak_bytes": max(_peaks(used))}
    rec = Run(setup_s=setup_s, window_s=window_s, units=units,
              peak_bytes=_peaks(used), peak=peak,
              work=load_file(BENCH / "work" / f"{cell.problem}.py",
                             f"work_{cell.problem}"))
    if trace:
        _reduce_trace(tdir, chips, rec, out, log)

    # the check: the sampled answers are on the host and the program's
    # results freed; the reference runs now
    t_ref = time.perf_counter()
    pairs, failure = sample.pairs(), sample.failure
    del cell
    numbers = {}
    if failure is None:
        try:
            numbers = check_pairs(config["problem"],
                                  float(traffic["eps"]), pairs)
        except Exception as e:  # noqa: BLE001 - the check could not run
            failure = f"{type(e).__name__}: {e}"
    limits = config["limits"]
    checks = {k: {"value": numbers.get(k), "limit": lim}
              for k, lim in limits.items()}
    correct = failure is None and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    print(f"reference: {len(pairs)} answers checked in "
          f"{time.perf_counter() - t_ref:.3f} s"
          + (f"; failed: {failure}" if failure else ""), file=log, flush=True)
    out.update(correct=bool(correct), checks=checks, run=rec,
               info={k: v for k, v in numbers.items() if k not in limits})
    return out
