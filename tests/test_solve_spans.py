"""Spans and program names inside the solve path.

* ``repro.obs.region`` marks the host prep (``solve.prepare``) and every
  chunk (``solve.chunk``) as ``repro.*`` annotations on the profiler's
  host timeline, records ``Span``s for a ``Tracer``'s sinks, and does
  nothing for ``obs=None``;
* the drivers' device programs are named ``jit_<spec>_<stage>`` (and
  ``jit_<spec>_mesh_<stage>`` on a mesh), so a device trace tells them
  apart.

The four-device mesh runs in a subprocess with forced host devices, as in
tests/test_distributed.py.
"""
import json
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compaction, distributed
from repro.core.api import ASSIGNMENT, OT, DispatchPolicy, solve
from repro.launch.mesh import make_batch_mesh
from repro.obs import InMemorySink, MetricsRegistry, Tracer, region, tracing

ROOT = Path(__file__).resolve().parents[1]
STAGES = compaction.STAGES


class Annotations:
    """Stand-in for ``jax.profiler.TraceAnnotation``: logs each enter and
    exit, and raises when ``forbid`` is set."""

    def __init__(self):
        self.log = []
        self.forbid = False

    def __call__(self, name, **kw):
        rec = self

        class _Annotation:
            def __enter__(self):
                if rec.forbid:
                    raise AssertionError(f"annotation {name} entered")
                rec.log.append(("enter", name))

            def __exit__(self, *exc):
                rec.log.append(("exit", name))

        return _Annotation()

    def entered(self, name=None):
        return [n for ev, n in self.log
                if ev == "enter" and (name is None or n == name)]


@pytest.fixture
def annotations(monkeypatch):
    rec = Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec)
    return rec


class ChunkLogLike:
    """An event-only emitter shaped like the benchmark's chunk log: its
    first parameter is called ``name``, so an event that passed a
    ``name=`` field would raise."""

    def __init__(self):
        self.events = []

    def event(self, name, **kw):
        self.events.append((name, kw))


def _ot_batch(b=5, n=12, seed=0):
    rng = np.random.default_rng(seed)
    c = rng.uniform(0.1, 1.0, (b, n, n)).astype(np.float32)
    nu = rng.dirichlet(np.ones(n), b).astype(np.float32)
    mu = rng.dirichlet(np.ones(n), b).astype(np.float32)
    return {"c": c, "nu": nu, "mu": mu}


_POLICIES = {
    "compact": DispatchPolicy(mode="compact", chunk=2, guaranteed=True),
    "mesh": DispatchPolicy(mode="mesh", chunk=2, guaranteed=True),
    "validate": DispatchPolicy(mode="compact", chunk=2, validate=True),
    "hybrid": DispatchPolicy(mode="compact", chunk=2, solver="hybrid"),
}
# solve.prepare regions of one call: the front door's solver routing, the
# admission check where asked, the compacting driver's prep (the mesh
# driver's, before and after its placement choice, in two)
_PREPARES = {"compact": 2, "mesh": 3, "validate": 3}


def _leaves_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


@pytest.mark.parametrize("mode", ["compact", "mesh"])
def test_tracer_spans_and_chunk_regions(mode, annotations):
    sink = InMemorySink()
    root = Tracer(MetricsRegistry(sinks=(sink,)))
    with root.span("solve", trace_id="t-1") as sp:
        solve(OT, _ot_batch(), 0.1, _POLICIES[mode],
              obs=root.bind(trace_id="t-1", parent=sp.span_id))
    spans = sink.spans()
    prep = [s for s in spans if s["name"] == "solve.prepare"]
    assert len(prep) == _PREPARES[mode]
    # nested under the caller's span; the chunks are events, not spans
    assert all(s["parent_id"] == sp.span_id and s["trace_id"] == "t-1"
               for s in prep)
    assert {s["name"] for s in spans} == {"solve", "solve.prepare"}
    chunks = sink.events("chunk")
    assert chunks and all(e["parent_id"] == sp.span_id for e in chunks)
    assert annotations.entered("repro.solve.chunk") == \
        ["repro.solve.chunk"] * len(chunks)
    assert annotations.entered("repro.solve.prepare") == \
        ["repro.solve.prepare"] * len(prep)
    assert annotations.entered() == ["repro.solve"] + [
        n for n in annotations.entered() if n != "repro.solve"]
    # regions of the solve path never overlap: each ends before the next
    inner = [(ev, n) for ev, n in annotations.log if n != "repro.solve"]
    assert inner == [e for n in annotations.entered()[1:]
                     for e in (("enter", n), ("exit", n))]


@pytest.mark.parametrize("case", sorted(_POLICIES))
def test_event_only_emitter_runs_the_traced_path(case, annotations):
    obs = ChunkLogLike()
    solve(OT, _ot_batch(seed=1), 0.1, _POLICIES[case], want=("cost",),
          obs=obs)
    kinds = {k for k, _ in obs.events}
    assert kinds == {"chunk"}
    chunk_events = sum(k == "chunk" for k, _ in obs.events)
    assert len(annotations.entered("repro.solve.chunk")) == chunk_events
    assert annotations.entered("repro.solve.prepare")
    if case in _PREPARES:
        assert len(annotations.entered("repro.solve.prepare")) == \
            _PREPARES[case]


@pytest.mark.parametrize("mode", ["compact", "mesh"])
@pytest.mark.parametrize("spec", ["assignment", "ot"])
def test_obs_none_enters_nothing_and_is_bit_identical(spec, mode,
                                                      annotations,
                                                      monkeypatch):
    sp = ASSIGNMENT if spec == "assignment" else OT
    inputs = _ot_batch(seed=2)
    if sp is ASSIGNMENT:
        inputs = {"c": inputs["c"]}
    traced, _ = solve(sp, inputs, 0.1, _POLICIES[mode],
                      obs=Tracer(MetricsRegistry()))
    assert annotations.entered()

    def no_span(*a, **kw):
        raise AssertionError("span built for obs=None")

    annotations.forbid = True
    monkeypatch.setattr(tracing.Span, "__init__", no_span)
    plain, _ = solve(sp, inputs, 0.1, _POLICIES[mode])
    assert _leaves_equal(traced, plain)


def test_region_helper_dispatches_on_obs(annotations):
    sink = InMemorySink()
    tr = Tracer(MetricsRegistry(sinks=(sink,)))
    with region(None, "a"):
        pass
    with region(ChunkLogLike(), "b"):
        pass
    with region(tr, "c"):
        pass
    with region(tr, "d", record=False):
        pass
    assert annotations.entered() == ["repro.b", "repro.c", "repro.d"]
    assert [s["name"] for s in sink.spans()] == ["c"]


def test_regions_land_on_the_profiler_host_plane(tmp_path):
    """A real capture: the regions are host events of the trace, one
    ``repro.solve.chunk`` per chunk event."""
    obs = ChunkLogLike()
    inputs = _ot_batch(seed=3)
    solve(OT, inputs, 0.1, _POLICIES["compact"])       # compile outside
    with jax.profiler.trace(str(tmp_path)):
        solve(OT, inputs, 0.1, _POLICIES["compact"], obs=obs)
    (path,) = tmp_path.glob("**/*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(path))
    names = [e.name for plane in pd.planes if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events
             if e.name.startswith("repro.")]
    assert names.count("repro.solve.chunk") == len(obs.events) > 0
    assert names.count("repro.solve.prepare") == _PREPARES["compact"]


def _module_name(fn, *args) -> str:
    return re.search(r"module @(\S+)", fn.lower(*args).as_text()).group(1)


def _program_args(spec, fns):
    """Arguments for each of the five programs of ``fns``, from a tiny
    prepared (2, 4, 4) batch."""
    _, _, data, state = compaction._tiny_batch(spec.name)
    p = spec.prepare(spec.canonicalize(
        {kk: v for kk, v in _ot_batch(2, 4).items()
         if spec is OT or kk == "c"}), 0.25)
    ops = {kk: jnp.asarray(v) for kk, v in p.ops.items()}
    _, ctx = compaction.spec_fns(spec, 2)[0](ops)
    ctx = {**ctx, **{kk: ops[kk] for kk in spec.ctx_ops}}
    return [(ops,), (data, ctx), (data, state), (data, state), (ctx, state)]


@pytest.mark.parametrize("family", ["spec_fns", "mesh_fns"])
@pytest.mark.parametrize("spec", ["assignment", "ot"])
def test_programs_are_named_by_spec_and_stage(spec, family):
    sp = ASSIGNMENT if spec == "assignment" else OT
    if family == "spec_fns":
        fns, prefix = compaction.spec_fns(sp, 2), f"jit_{spec}_"
    else:
        fns = distributed._mesh_fns(sp, make_batch_mesh(), "data", 2)
        prefix = f"jit_{spec}_mesh_"
    got = [_module_name(f, *a) for f, a in zip(fns, _program_args(sp, fns))]
    assert got == [prefix + s for s in STAGES]


_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, re
import jax, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import distributed
from repro.core.api import OT, DispatchPolicy, solve
from repro.launch.mesh import make_batch_mesh
from repro.obs import InMemorySink, MetricsRegistry, Tracer

log = []

class Rec:
    def __init__(self, name, **kw):
        self.name = name
    def __enter__(self):
        log.append(self.name)
    def __exit__(self, *exc):
        pass

jax.profiler.TraceAnnotation = Rec
rng = np.random.default_rng(7)
b, n = 16, 12
inputs = {"c": rng.uniform(0.1, 1.0, (b, n, n)).astype(np.float32),
          "nu": rng.dirichlet(np.ones(n), b).astype(np.float32),
          "mu": rng.dirichlet(np.ones(n), b).astype(np.float32)}
eps = np.where(np.arange(b) % 3 == 0, 0.02, 0.2)
mesh = make_batch_mesh()
pol = DispatchPolicy(mode="mesh", mesh=mesh, placement="batch", chunk=1)
sink = InMemorySink()
traced, st = solve(OT, inputs, eps, pol,
                   obs=Tracer(MetricsRegistry(sinks=(sink,)), trace_id="m"))
n_traced = len(log)
plain, _ = solve(OT, inputs, eps, pol)
leaves = zip(jax.tree_util.tree_leaves(traced),
             jax.tree_util.tree_leaves(plain))
out = {
    "devices": int(mesh.shape["data"]),
    "devices_per_dispatch": st.devices_per_dispatch,
    "prepare_spans": [s["name"] for s in sink.spans()],
    "chunk_events": sink.count("chunk"),
    "chunk_regions": log.count("repro.solve.chunk"),
    "prepare_regions": log.count("repro.solve.prepare"),
    "untraced_annotations": len(log) - n_traced,
    "identical": all(np.array_equal(np.asarray(x), np.asarray(y))
                     for x, y in leaves),
}
sh = NamedSharding(mesh, P("data"))
p = OT.prepare(OT.canonicalize(inputs), 0.1, min_batch=4)
ops = {k: jax.device_put(v, sh) for k, v in p.ops.items()}
fns = distributed._mesh_fns(OT, mesh, "data", 1)
data, ctx = fns[0](ops)
ctx = {**ctx, **{k: ops[k] for k in OT.ctx_ops}}
state = fns[1](data, ctx)
args = [(ops,), (data, ctx), (data, state), (data, state), (ctx, state)]
out["names"] = [re.search(r"module @(\S+)", f.lower(*a).as_text()).group(1)
                for f, a in zip(fns, args)]
print("RESULT:" + json.dumps(out))
"""


def test_mesh_spans_on_four_host_devices():
    proc = subprocess.run(
        [sys.executable, "-c", _MESH_SCRIPT],
        capture_output=True, text=True, timeout=900, cwd=str(ROOT),
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin",
             "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT:")]
    assert line, proc.stdout
    out = json.loads(line[0][len("RESULT:"):])
    assert out["devices"] == 4
    # the loop ran sharded over the mesh, then collapsed to one chip
    assert out["devices_per_dispatch"][0] == 4
    assert out["prepare_spans"] == ["solve.prepare"] * 3
    assert out["chunk_regions"] == out["chunk_events"] > 1
    assert out["prepare_regions"] == 3
    assert out["untraced_annotations"] == 0
    assert out["identical"]
    assert out["names"] == ["jit_ot_mesh_" + s for s in STAGES]
