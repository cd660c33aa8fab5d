"""The check that decides ``correct``, driven through the harness on the
CPU at test sizes: sound runs pass; the control (the program's own
unguaranteed path, eps instead of eps / 3) and each fault a cell can have,
planted under the timed path, come out not correct."""
import io
import json
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

import drive
from repro.core import api, compaction, distributed
from repro.core.api import ASSIGNMENT, OT

BENCH = drive.BENCH


def _config(name: str, **over) -> dict:
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    cfg.update(over)
    return cfg


# the four cell kinds of BENCHMARK.json, cut to test sizes
CASES = {
    "assignment": (_config("synth-assign-10k", n=64),
                   {"mode": "single", "pool": 3, "eps": 0.05, "sample": 2,
                    "trace_units": 2}, 1),
    "ot_single": (_config("dotmark-whitenoise"),
                  {"mode": "single", "resolution": 6, "pool": 3, "eps": 0.1,
                   "sample": 2, "trace_units": 2}, 1),
    "ot_batch": (_config("dotmark-whitenoise"),
                 {"mode": "batch", "resolution": 6, "pairs": 6, "eps": 0.05,
                  "sample": 1, "trace_units": 1}, 1),
    "ot_mesh": (_config("dotmark-whitenoise"),
                {"mode": "batch", "resolution": 6, "pairs": 16, "eps": 0.02,
                 "sample": 1, "trace_units": 1}, 4),
}


@pytest.fixture(autouse=True)
def fresh_programs():
    """Planted faults change the solver's functions: rebuild the jitted
    programs before and after each test."""
    for f in (compaction.spec_fns, distributed._mesh_fns,
              distributed._place_into):
        f.cache_clear()
    yield
    for f in (compaction.spec_fns, distributed._mesh_fns,
              distributed._place_into):
        f.cache_clear()


def _run(case: str, trace: bool = False, **kw) -> dict:
    config, traffic, chips = CASES[case]
    return drive.run(config, traffic, chips=chips, seed=2 ** 31 + 11,
                     seconds=0.2, trace=trace, devices=jax.devices(),
                     peak={"hbm_bytes_per_s": 819e9}, t0=time.perf_counter(),
                     log=io.StringIO(), **kw)


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("trace", [False, True])
def test_sound_runs_are_correct(case, trace):
    out = _run(case, trace=trace)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    # the reference gave every number it names
    ref = drive.reference(CASES[case][0]["problem"])
    assert set(out["checks"]) | set(out["info"]) == set(ref.NUMBERS)


@pytest.mark.parametrize("case", sorted(CASES))
def test_control_is_not_correct(case):
    out = _run(case, control=True)
    assert not out["correct"]
    assert out["checks"]["dual_viol"]["value"] > 0


def _unchanged(monkeypatch):
    for spec in (ASSIGNMENT, OT):
        monkeypatch.setattr(spec, "run_phases", lambda data, state, k: state)


def _half_left_out(monkeypatch):
    real = api.solve

    def solve(spec, inst, eps, policy, **kw):
        b = inst["c"].shape[0]
        h = b // 2
        inst = {k: jnp.concatenate([v[:b - h], v[:h]]) for k, v in
                inst.items()}
        return real(spec, inst, eps, policy, **kw)

    monkeypatch.setattr(api, "solve", solve)


def _no_exchange(monkeypatch):
    """Each chip writes back lanes from its own part of a retiring bucket
    only: the bucket's replication across chips is left out."""
    def place_into(mesh, axis):
        d = int(mesh.shape[axis])

        def place(buf, tree, pos):
            i = jax.lax.axis_index(axis)

            def one(b, a):
                k = max(a.shape[0] // d, 1)
                own = jax.lax.dynamic_slice_in_dim(
                    a, jnp.minimum(i * k, a.shape[0] - k), k)
                keep = (pos >= 0).reshape(pos.shape + (1,) * (b.ndim - 1))
                return jnp.where(keep, own[jnp.maximum(pos, 0) % k], b)

            return jax.tree_util.tree_map(one, buf, tree)

        return jax.jit(jax.shard_map(place, mesh=mesh,
                                     in_specs=(P(axis), P(), P(axis)),
                                     out_specs=P(axis)))

    monkeypatch.setattr(distributed, "_place_into", place_into)


def _altered(monkeypatch):
    def swap(x):
        return x.at[0].set(x[1]).at[1].set(x[0])

    a_epi, o_epi = type(ASSIGNMENT).epilogue, type(OT).epilogue
    monkeypatch.setattr(ASSIGNMENT, "epilogue", lambda ctx, state: (
        lambda r: r._replace(matching=swap(r.matching)))(
            a_epi(ASSIGNMENT, ctx, state)))
    monkeypatch.setattr(OT, "epilogue", lambda ctx, state: (
        lambda r: r._replace(plan=swap(r.plan)))(o_epi(OT, ctx, state)))


FAULTS = {"state_unchanged": _unchanged, "half_batch_left_out":
          _half_left_out, "exchange_left_out": _no_exchange,
          "answer_altered": _altered}
# the faults each cell kind can have
HAS = {"assignment": ("state_unchanged", "answer_altered"),
       "ot_single": ("state_unchanged", "answer_altered"),
       "ot_batch": ("state_unchanged", "half_batch_left_out",
                    "answer_altered"),
       "ot_mesh": ("state_unchanged", "half_batch_left_out",
                   "exchange_left_out", "answer_altered")}


@pytest.mark.parametrize("case,fault", [(c, f) for c in sorted(HAS)
                                        for f in HAS[c]])
def test_fault_is_not_correct(case, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    assert not _run(case)["correct"]


def _bench_run(root, tmp_path):
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "HOME": str(tmp_path), "TMPDIR": str(tmp_path)}
    return subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), "--workload",
         "assign10k.eps0.05", "--seed", "1", "--seconds", "1", "--trace",
         "0"], capture_output=True, text=True, env=env, timeout=300)


def test_no_result_without_a_tpu(tmp_path):
    p = _bench_run(drive.ROOT, tmp_path)
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_no_result_without_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(drive.ROOT / "BENCHMARK.json", tmp_path)
    p = _bench_run(tmp_path, tmp_path)
    assert p.returncode != 0 and p.stdout == ""
