"""OT padding is masked inside the solve programs, not by the host prep.

A ragged OT batch carries each instance in the leading ``sizes[i]`` block
of a (B, M, N) bucket. ``OTSpec.prepare`` passes the caller's ``c``,
``nu`` and ``mu`` through and hands each lane's ``m_valid``/``n_valid``
to the prologue and epilogue programs, which zero the padding there
(``problem.mask_ot_padding``). These tests hold that contract:

  * whatever the padding holds (1e30, inf, NaN costs; unit masses) gives
    the same answer, bit for bit, as zero padding — under compact,
    lockstep, the fused kernel and batch placement on a forced host mesh
    — and the same phases, rounds, flows and duals as ``solve_ot`` on
    each unpadded instance: the mask reaches ``max(c)`` in the prologue
    and ``sum(plan * c)`` in the epilogue;
  * ``prepare`` builds no (B, M, N) array: ``ops["c"]`` is the caller's
    buffer, and no host array in ``ops`` is larger than one mass row per
    lane.
"""
import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.api import FUSED_OT, OT, DispatchPolicy, solve
from repro.core.transport import solve_ot

ROOT = Path(__file__).resolve().parents[1]

SIZES = np.asarray([[24, 24], [17, 21], [20, 13], [9, 24], [24, 16]],
                   np.int32)
EPS = 0.2


def _ragged(seed=0, m=24, n=24):
    """Zero-padded ragged batch; lane 0 is full size."""
    rng = np.random.default_rng(seed)
    b = len(SIZES)
    c = np.zeros((b, m, n), np.float32)
    nu = np.zeros((b, m), np.float32)
    mu = np.zeros((b, n), np.float32)
    for i, (mi, ni) in enumerate(SIZES):
        c[i, :mi, :ni] = rng.uniform(0.05, 1.0, (mi, ni))
        nu[i, :mi] = rng.dirichlet(np.ones(mi))
        mu[i, :ni] = rng.dirichlet(np.ones(ni))
    return c, nu, mu


def _garbage(c, nu, mu):
    """The same batch with 1e30 / inf / NaN costs and unit masses in every
    lane's padding."""
    c, nu, mu = c.copy(), nu.copy(), mu.copy()
    _, m, n = c.shape
    fill = np.asarray([1e30, np.inf, np.nan], np.float32)
    for i, (mi, ni) in enumerate(SIZES):
        pad = ~((np.arange(m) < mi)[:, None] & (np.arange(n) < ni)[None, :])
        c[i][pad] = fill[np.arange(int(pad.sum())) % 3]
        nu[i, mi:] = 1.0
        mu[i, ni:] = 1.0
    return c, nu, mu


def _assert_identical(a, b, tag):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=tag)


_POLICIES = {
    "compact": DispatchPolicy(mode="compact", chunk=3),
    "lockstep": DispatchPolicy(mode="lockstep"),
    "fused": DispatchPolicy(mode="compact", chunk=3, fused=True),
}


@pytest.mark.parametrize("mode", sorted(_POLICIES))
def test_padding_garbage_is_masked(mode):
    c, nu, mu = _ragged()
    cg, nug, mug = _garbage(c, nu, mu)
    assert np.isnan(cg).any() and np.isinf(cg).any()
    pol = _POLICIES[mode]
    zero, _ = solve(OT, {"c": c, "nu": nu, "mu": mu}, EPS, pol, sizes=SIZES)
    junk, _ = solve(OT, {"c": cg, "nu": nug, "mu": mug}, EPS, pol,
                    sizes=SIZES)
    # plan, cost, duals, phases, rounds, state: all bit-identical
    _assert_identical(zero, junk, mode)
    assert np.isfinite(np.asarray(junk.cost)).all()
    for i, (mi, ni) in enumerate(SIZES):
        s = solve_ot(jnp.asarray(c[i, :mi, :ni]), jnp.asarray(nu[i, :mi]),
                     jnp.asarray(mu[i, :ni]), EPS)
        assert int(junk.phases[i]) == int(s.phases), (mode, i)
        assert int(junk.rounds[i]) == int(s.rounds), (mode, i)
        np.testing.assert_array_equal(np.asarray(junk.y_b)[i, :mi],
                                      np.asarray(s.y_b))
        np.testing.assert_array_equal(np.asarray(junk.y_a)[i, :ni],
                                      np.asarray(s.y_a))
        np.testing.assert_array_equal(
            np.asarray(junk.state.f_hi)[i, :mi, :ni],
            np.asarray(s.state.f_hi))
        np.testing.assert_array_equal(
            np.asarray(junk.state.f_lo)[i, :mi, :ni],
            np.asarray(s.state.f_lo))
        # the float plan only to the batched-vs-unbatched reassociation
        # tolerance of tests/test_batched.py: one-instance solve_ot is a
        # differently shaped program
        np.testing.assert_allclose(np.asarray(junk.plan)[i, :mi, :ni],
                                   np.asarray(s.plan), atol=1e-6)
        assert float(junk.cost[i]) == pytest.approx(float(s.cost), abs=2e-6)
        assert not np.asarray(junk.plan)[i, mi:].any()
        assert not np.asarray(junk.plan)[i, :, ni:].any()


_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys
import jax, numpy as np
sys.path.insert(0, "tests")
from test_ot_padding import EPS, SIZES, _garbage, _ragged
from repro.core.api import OT, DispatchPolicy, solve
from repro.launch.mesh import make_batch_mesh

mesh = make_batch_mesh()
pol = DispatchPolicy(mode="mesh", mesh=mesh, placement="batch", chunk=2)
c, nu, mu = _ragged()
cg, nug, mug = _garbage(c, nu, mu)
junk, st = solve(OT, {"c": cg, "nu": nug, "mu": mug}, EPS, pol, sizes=SIZES)
zero, _ = solve(OT, {"c": c, "nu": nu, "mu": mu}, EPS, pol, sizes=SIZES)
comp, _ = solve(OT, {"c": c, "nu": nu, "mu": mu}, EPS,
                DispatchPolicy(mode="compact", chunk=2), sizes=SIZES)
same = lambda a, b: all(
    np.array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)))
print("RESULT:" + json.dumps({
    "devices": int(mesh.shape["data"]),
    "placement": st.placement,
    "devices_per_dispatch": st.devices_per_dispatch,
    "garbage_equals_zero": same(junk, zero),
    "mesh_equals_compact": same(zero, comp),
}))
"""


def test_padding_garbage_is_masked_mesh_batch():
    proc = subprocess.run(
        [sys.executable, "-c", _MESH_SCRIPT],
        capture_output=True, text=True, timeout=900, cwd=str(ROOT),
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin",
             "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT:")]
    assert line, proc.stdout
    out = json.loads(line[0][len("RESULT:"):])
    assert out["devices"] == 4 and out["placement"] == "batch", out
    assert out["devices_per_dispatch"][0] == 4, out
    assert out["garbage_equals_zero"], out
    assert out["mesh_equals_compact"], out


@pytest.mark.parametrize("spec", [OT, FUSED_OT], ids=["ot", "fused_ot"])
@pytest.mark.parametrize("ragged", [False, True], ids=["full", "ragged"])
def test_ot_prepare_builds_no_batch_array(spec, ragged):
    """With bp == b, ``prepare`` hands the caller's cost buffer through
    and adds only per-lane vectors: no host (B, M, N) mask comes back."""
    c, nu, mu = _ragged()
    b, m, n = c.shape[0] - 1, c.shape[1], c.shape[2]   # b = 4, a power of 2
    inputs = spec.canonicalize({"c": c[:b], "nu": nu[:b], "mu": mu[:b]})
    sizes = SIZES[:b] if ragged else None
    p = spec.prepare(inputs, EPS, sizes=sizes)
    assert p.bp == b
    assert p.ops["c"] is inputs["c"]
    assert p.ops["nu"] is inputs["nu"] and p.ops["mu"] is inputs["mu"]
    for key in ("m_valid", "n_valid"):
        assert np.shape(p.ops[key]) == (b,), key
    np.testing.assert_array_equal(
        p.ops["m_valid"], SIZES[:b, 0] if ragged else np.full(b, m))
    np.testing.assert_array_equal(
        p.ops["n_valid"], SIZES[:b, 1] if ragged else np.full(b, n))
    for key, v in p.ops.items():
        if isinstance(v, np.ndarray):
            assert v.size <= b * max(m, n), (key, v.shape)
