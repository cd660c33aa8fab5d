"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics and the result's ``breakdown`` read.

What a TPU trace holds (read by hand on a v5e, kept in
``bench/tests/data``): one plane ``/device:TPU:<i>`` per chip with an
``XLA Modules`` line (one event per program execution, named
``jit_<function>(<fingerprint>)``) and an ``XLA Ops`` line (one event per
operation, named by its HLO text); the host plane ``/host:CPU`` holds one
``PJRT_LoadedExecutable_Execute`` event per program launch and the
benchmark's own ``TraceAnnotation`` spans (names starting ``bench.``).

The program's five per-solve programs (``compaction.spec_fns``) are all
jitted lambdas, so they share the module name ``jit__lambda`` and differ
only by fingerprint. The phase-loop programs are therefore found from the
program's own chunk events instead: every ``"chunk"`` event the driver
emits is marked on the host (``bench.obs.chunk``) right after the chunk's
converged-mask fetch returned. The driver launches exactly two programs
per chunk, the chunk and then its converged-mask check, so the last two
launches before each mark are the phase loop's. Launches and executions
are matched by order on device 0, which runs every program the
benchmark's cells launch; the device clock is shifted so that no
execution starts before its launch (on a v5e it reads up to ~1.3 ms
behind the host's).
"""
from __future__ import annotations

import gzip
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

LAUNCH = "PJRT_LoadedExecutable_Execute"
CHUNK_MARK = "bench.obs.chunk"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_FINGERPRINT = re.compile(r"\(\d+\)$")


@dataclass
class Device:
    """One chip's executions: ``modules`` and ``ops`` are (k, 2) arrays of
    [start, end) ns on the host's clock."""
    index: int
    modules: np.ndarray
    module_names: List[str]
    ops: np.ndarray
    op_names: List[str]


@dataclass
class Trace:
    devices: List[Device]
    launches: np.ndarray                    # host ns of each launch
    marks: List[Tuple[str, float, float]]   # bench.* spans (name, lo, hi)
    shift_ns: float                         # added to device times


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def _array(evs) -> np.ndarray:
    return np.asarray([(a, b) for _, a, b in evs], np.float64).reshape(-1, 2)


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` (or ``.xplane.pb.gz``) file."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    if path.endswith(".gz"):
        raw = gzip.decompress(raw)
    pd = ProfileData.from_serialized_xspace(raw)
    devices, launches, marks = [], [], []
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m:
            lines = {ln.name: _events(ln) for ln in plane.lines}
            mods = sorted(lines.get("XLA Modules", []), key=lambda e: e[1])
            ops = sorted(lines.get("XLA Ops", []), key=lambda e: e[1])
            devices.append(Device(
                index=int(m.group(1)), modules=_array(mods),
                module_names=[e[0] for e in mods], ops=_array(ops),
                op_names=[e[0] for e in ops]))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for name, lo, hi in _events(line):
                    if name == LAUNCH:
                        launches.append(lo)
                    elif name.startswith("bench."):
                        marks.append((name, lo, hi))
    devices.sort(key=lambda d: d.index)
    launches = np.sort(np.asarray(launches, np.float64))
    shift = 0.0
    if devices and len(devices[0].modules) == len(launches) > 0:
        shift = max(0.0, float(np.max(launches - devices[0].modules[:, 0])))
    for d in devices:
        d.modules = d.modules + shift
        d.ops = d.ops + shift
    marks.sort(key=lambda m: m[1])
    return Trace(devices=devices, launches=launches, marks=marks,
                 shift_ns=shift)


def union(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """Merged [start, end) intervals of ``iv`` clipped to [lo, hi)."""
    if len(iv) == 0:
        return np.zeros((0, 2))
    iv = np.clip(iv[np.argsort(iv[:, 0])], lo, hi)
    iv = iv[iv[:, 1] > iv[:, 0]]
    out: List[List[float]] = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return np.asarray(out).reshape(-1, 2)


def busy_ns(dev: Device, windows: np.ndarray) -> float:
    """Time inside the (k, 2) [start, end) ``windows`` in which an
    operation ran on ``dev``."""
    total = 0.0
    for lo, hi in windows:
        u = union(dev.ops, lo, hi)
        total += float(np.sum(u[:, 1] - u[:, 0]))
    return total


def chunk_spans(trace: Trace, chunk_s: Sequence[float]) -> np.ndarray:
    """(k, 2) host spans of the driver's chunks: the k-th chunk mark paired
    with the k-th chunk event's ``chunk_s``."""
    ends = [lo for name, lo, _ in trace.marks if name == CHUNK_MARK]
    if len(ends) != len(chunk_s):
        raise ValueError(f"{len(ends)} chunk marks in the trace against "
                         f"{len(chunk_s)} chunk events")
    ends = np.asarray(ends, np.float64)
    return np.stack([ends - np.asarray(chunk_s) * 1e9, ends], axis=1)


def phase_loop_programs(trace: Trace) -> Optional[set]:
    """Module names (with fingerprint) of the two launches before each
    chunk mark; None where launches and device-0 executions do not pair
    or no chunk was marked."""
    marks = [lo for name, lo, _ in trace.marks if name == CHUNK_MARK]
    if not trace.devices or not marks:
        return None
    d0 = trace.devices[0]
    if len(d0.modules) != len(trace.launches):
        return None
    last = np.searchsorted(trace.launches, np.asarray(marks)) - 1
    if (last < 1).any():
        return None
    return {d0.module_names[i] for j in last for i in (j - 1, j)}


def module_ns(trace: Trace, names: set, windows: np.ndarray) -> float:
    """Device time of executions of ``names`` inside ``windows``, summed
    over devices."""
    total = 0.0
    for d in trace.devices:
        for (a, b), name in zip(d.modules, d.module_names):
            if name in names:
                for lo, hi in windows:
                    total += max(0.0, min(b, hi) - max(a, lo))
    return total


def _module_of_ops(dev: Device) -> List[int]:
    """Index of the module execution that contains each op (-1: none)."""
    out, j = [], 0
    for a, _ in dev.ops:
        while j < len(dev.modules) and dev.modules[j, 1] < a:
            j += 1
        out.append(j if j < len(dev.modules) and dev.modules[j, 0] <= a
                   else -1)
    return out


def _self_ns(ops: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """Each op's time in [lo, hi) not covered by the ops nested inside it
    (a ``while`` op spans its body's ops on the same line)."""
    own = np.maximum(0.0, np.minimum(ops[:, 1], hi) - np.maximum(ops[:, 0],
                                                                 lo))
    order = np.lexsort((-ops[:, 1], ops[:, 0]))
    stack: List[int] = []
    for k in order:
        while stack and ops[stack[-1], 1] <= ops[k, 0]:
            stack.pop()
        if stack:
            own[stack[-1]] -= own[k]
        stack.append(k)
    return np.maximum(own, 0.0)


def _short(op_name: str) -> str:
    return op_name.split(" = ")[0].lstrip("%")


def device_ops(trace: Trace, loop: set, windows: np.ndarray,
               top: int = 10) -> List[List]:
    """The operations that took most device time (self time) inside
    ``windows``, summed over devices, named ``<program>/<op>``;
    ``phase_loop`` names the programs launched for the driver's chunks."""
    acc: Dict[str, float] = {}
    for d in trace.devices:
        if not len(d.ops):
            continue
        own = sum(_self_ns(d.ops, lo, hi) for lo, hi in windows)
        for t, name, j in zip(own, d.op_names, _module_of_ops(d)):
            if t <= 0:
                continue
            mod = d.module_names[j] if j >= 0 else "?"
            prog = "phase_loop" if mod in loop else _FINGERPRINT.sub("", mod)
            key = f"{prog}/{_short(name)}"
            acc[key] = acc.get(key, 0.0) + t
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v * 1e-9] for k, v in ranked]


def _host_label(trace: Trace, spans: np.ndarray, t: float) -> str:
    if any(lo <= t <= hi for lo, hi in spans):
        return "phase loop: host sync between chunks"
    inner = None
    for name, lo, hi in trace.marks:
        if lo <= t <= hi and name != CHUNK_MARK:
            if inner is None or hi - lo < inner[2] - inner[1]:
                inner = (name, lo, hi)
    if inner is None:
        return "between units"
    name = inner[0][len("bench."):]
    if name == "solve":
        lo, hi = inner[1], inner[2]
        inside = [s for s in spans if s[0] >= lo and s[1] <= hi]
        if not inside:
            return "solve: no chunk"
        return ("solve: before the phase loop" if t < inside[0][0]
                else "solve: after the phase loop")
    return name


def idle_gaps(trace: Trace, spans: np.ndarray, windows: np.ndarray,
              top: int = 10) -> List[List]:
    """Device idle time inside ``windows``, summed per host activity at
    the middle of each gap; device 0, or the mean over devices where
    several ran (each gap labelled by the same host activities)."""
    acc: Dict[str, float] = {}
    for d in trace.devices:
        for lo, hi in windows:
            u = union(d.ops, lo, hi)
            edges = np.concatenate([[lo], u.ravel(), [hi]]).reshape(-1, 2)
            for a, b in edges:
                if b > a:
                    key = _host_label(trace, spans, 0.5 * (a + b))
                    acc[key] = acc.get(key, 0.0) + (b - a) / len(
                        trace.devices)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v * 1e-9] for k, v in ranked]
