"""The four-chip batch-placement deployment (DOTmark WhiteNoise pairs in
one call, sharded by batch), at a small size.

32 DOTmark-style grid pairs at 8 x 8 run through ``solve`` with
``DispatchPolicy(mode="mesh", placement="batch")`` on four forced host
devices, in a subprocess as in tests/test_ot_padding.py. The descent
passes through a sharded re-bucket and the collapse to one device. Every
lane is certified and within eps * max(c) of the exact LP cost
(``core/exact.py``), and every result is bit-identical to the one-device
compacting driver. The drivers' counters of what the mesh adds:
``rebucket_s`` (host seconds of the re-bucket since the previous chunk
event) and ``chip_phases`` (each device's local max phase delta).
"""
import json
import subprocess
import sys
from pathlib import Path

import numpy as np

from repro.core.api import OT, DispatchPolicy, solve

ROOT = Path(__file__).resolve().parents[1]
EPS = 0.1


def grid_pairs(side: int, b: int, seed: int = 20170601):
    """The first ``b`` of DOTmark's (i < j) pairs of 10 white-noise images
    on a ``side`` x ``side`` grid, squared-Euclidean cost."""
    rng = np.random.default_rng(seed)
    n = side * side
    idx = np.arange(n)
    p = np.stack([idx // side, idx % side], 1) / (side - 1)
    c = ((p[:, None, :] - p[None, :, :]) ** 2).sum(-1).astype(np.float32)
    img = rng.uniform(size=(10, n))
    img = (img / img.sum(1, keepdims=True)).astype(np.float32)
    pairs = [(i, j) for i in range(10) for j in range(i + 1, 10)][:b]
    a = np.asarray([i for i, _ in pairs])
    z = np.asarray([j for _, j in pairs])
    return {"c": np.broadcast_to(c, (b, n, n)).copy(), "nu": img[a],
            "mu": img[z]}


class ChunkEvents:
    """An ``obs`` that keeps the drivers' ``"chunk"`` events."""

    def __init__(self):
        self.chunks = []

    def event(self, name, **kw):
        if name == "chunk":
            self.chunks.append(kw)


def check_rebucket_counter(chunks):
    """``rebucket_s`` is never negative, and positive exactly on the
    chunks that follow a re-bucket (an occupancy change: the bucket
    differs from the previous chunk's)."""
    assert chunks
    for i, e in enumerate(chunks):
        assert e["rebucket_s"] >= 0.0, (i, e)
        moved = i > 0 and e["bucket"] != chunks[i - 1]["bucket"]
        assert (e["rebucket_s"] > 0.0) == moved, (i, e)


_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys
import jax, numpy as np
sys.path.insert(0, "tests")
from test_mesh_deployment import EPS, ChunkEvents, grid_pairs
from repro.core.api import OT, DispatchPolicy, solve
from repro.core.exact import exact_ot_cost
from repro.launch.mesh import make_batch_mesh

mesh = make_batch_mesh()
inputs = grid_pairs(8, 32)
want = ("cost", "duals", "plan")
log = ChunkEvents()
sb = solve(OT, inputs, EPS, DispatchPolicy(
    mode="mesh", mesh=mesh, placement="batch", chunk=2, guaranteed=True),
    want=want, obs=log)
ref = solve(OT, inputs, EPS, DispatchPolicy(
    mode="compact", chunk=2, guaranteed=True), want=want)
fields = {"plan": lambda s: s.plan(), "cost": lambda s: s.cost(),
          "duals": lambda s: np.stack(s.duals()),
          "phases": lambda s: s.phases(), "rounds": lambda s: s.rounds()}
st = sb.driver_stats
print("RESULT:" + json.dumps({
    "devices": int(mesh.shape["data"]),
    "placement": st.placement,
    "collapsed_at": st.collapsed_at,
    "chunks": log.chunks,
    "feasible": sb.dual_feasible().tolist(),
    "gap_ok": (sb.additive_gap() <= sb.additive_gap_bound()).tolist(),
    "cost": sb.cost().tolist(),
    "exact": [exact_ot_cost(c, nu, mu) for c, nu, mu in zip(
        inputs["c"], inputs["nu"], inputs["mu"])],
    "max_c": inputs["c"].max(axis=(1, 2)).tolist(),
    "identical": {k: bool(np.array_equal(f(sb), f(ref)))
                  for k, f in fields.items()},
}))
"""


def test_mesh_batch_deployment_on_four_host_devices():
    proc = subprocess.run(
        [sys.executable, "-c", _MESH_SCRIPT],
        capture_output=True, text=True, timeout=900, cwd=str(ROOT),
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin",
             "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT:")]
    assert line, proc.stdout
    out = json.loads(line[0][len("RESULT:"):])
    assert out["devices"] == 4 and out["placement"] == "batch"
    chunks = out["chunks"]
    devices = [e["devices"] for e in chunks]
    # sharded first, then collapsed to one device for the tail
    assert devices[0] == 4 and devices[-1] == 1
    assert devices == sorted(devices, reverse=True)
    assert out["collapsed_at"] is not None and out["collapsed_at"] < 4
    # at least one re-bucket from the mesh onto the mesh, and the collapse
    assert any(e["devices"] == 4 and e["rebucket_s"] > 0 for e in chunks)
    first_one = devices.index(1)
    assert chunks[first_one]["rebucket_s"] > 0
    check_rebucket_counter(chunks)
    for e in chunks:
        assert len(e["chip_phases"]) == e["devices"]
        assert max(e["chip_phases"]) == e["phases"]
    # every lane certified, and within eps * max(c) of the exact cost
    assert all(out["feasible"]) and all(out["gap_ok"])
    for cost, opt, mc in zip(out["cost"], out["exact"], out["max_c"]):
        assert opt - 1e-6 <= cost <= opt + EPS * mc, (cost, opt, mc)
    # batch placement is bit-identical to the one-device compacting driver
    assert out["identical"] == {k: True for k in out["identical"]}


def test_rebucket_counter_on_the_one_device_driver():
    inputs = grid_pairs(6, 8)
    log = ChunkEvents()
    sb = solve(OT, inputs, EPS, DispatchPolicy(mode="compact", chunk=2,
                                               guaranteed=True),
               want=("cost",), obs=log)
    buckets = [e["bucket"] for e in log.chunks]
    assert len(set(buckets)) >= 3, buckets      # descended at least twice
    check_rebucket_counter(log.chunks)
    assert all("chip_phases" not in e for e in log.chunks)
    assert len(log.chunks) == sb.driver_stats.dispatches
