"""Batched fixed-shape solvers: padded/bucketed batch results must equal
independent unbatched solves (bit-identical matchings, f32-tolerance costs),
the batched Pallas kernel must match the per-instance kernel, and the
reworked OTService must bucket a mixed queue correctly."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.batched import (
    bucket_instances,
    next_bucket,
    pad_stack,
    solve_assignment_batched,
    solve_assignment_ragged,
    solve_ot_batched,
    solve_ot_ragged,
)
from repro.core.costs import build_cost_matrix
from repro.core.pushrelabel import solve_assignment
from repro.core.transport import solve_ot


def _ragged_ot_instances(b, lo, hi, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(b):
        m, n = int(rng.integers(lo, hi)), int(rng.integers(lo, hi))
        x = rng.uniform(size=(m, 2))
        y = rng.uniform(size=(n, 2))
        c = np.asarray(build_cost_matrix(x, y, "euclidean"))
        nu = rng.dirichlet(np.ones(m)).astype(np.float32)
        mu = rng.dirichlet(np.ones(n)).astype(np.float32)
        out.append((c, nu, mu))
    return out


def _pad_batch(insts, mb, nb):
    b = len(insts)
    c = np.zeros((b, mb, nb), np.float32)
    nu = np.zeros((b, mb), np.float32)
    mu = np.zeros((b, nb), np.float32)
    sizes = np.zeros((b, 2), np.int32)
    for i, (ci, nui, mui) in enumerate(insts):
        m, n = ci.shape
        c[i, :m, :n] = ci
        nu[i, :m] = nui
        mu[i, :n] = mui
        sizes[i] = (m, n)
    return c, nu, mu, sizes


def test_solve_ot_batched_matches_unbatched():
    """Acceptance: B=8 padded instances == 8 independent solve_ot calls."""
    insts = _ragged_ot_instances(8, 24, 64, seed=3)
    c, nu, mu, sizes = _pad_batch(insts, 64, 64)
    r = solve_ot_batched(c, nu, mu, 0.1, sizes=sizes)
    for i, (ci, nui, mui) in enumerate(insts):
        s = solve_ot(jnp.asarray(ci), jnp.asarray(nui), jnp.asarray(mui), 0.1)
        assert float(r.cost[i]) == pytest.approx(float(s.cost), abs=2e-6)
        m, n = ci.shape
        np.testing.assert_allclose(
            np.asarray(r.plan)[i, :m, :n], np.asarray(s.plan), atol=1e-6
        )
        # padding carries no mass
        assert float(np.abs(np.asarray(r.plan)[i, m:, :]).sum()) == 0.0
        assert float(np.abs(np.asarray(r.plan)[i, :, n:]).sum()) == 0.0
        assert int(r.phases[i]) == int(s.phases)


def test_solve_ot_batched_marginals_exact():
    insts = _ragged_ot_instances(4, 16, 40, seed=11)
    c, nu, mu, sizes = _pad_batch(insts, 40, 40)
    r = solve_ot_batched(c, nu, mu, 0.05, sizes=sizes)
    plan = np.asarray(r.plan)
    np.testing.assert_allclose(plan.sum(2), nu, atol=2e-6)
    np.testing.assert_allclose(plan.sum(1), mu, atol=2e-6)


def test_solve_assignment_batched_matches_unbatched():
    rng = np.random.default_rng(7)
    cs = []
    for _ in range(6):
        m = int(rng.integers(20, 96))
        n = int(rng.integers(m, 96))          # m <= n
        x = rng.uniform(size=(m, 2))
        y = rng.uniform(size=(n, 2))
        cs.append(np.asarray(build_cost_matrix(x, y, "euclidean")))
    mb = max(c.shape[0] for c in cs)
    nb = max(c.shape[1] for c in cs)
    c = np.zeros((len(cs), mb, nb), np.float32)
    sizes = np.zeros((len(cs), 2), np.int32)
    for i, ci in enumerate(cs):
        c[i, :ci.shape[0], :ci.shape[1]] = ci
        sizes[i] = ci.shape
    r = solve_assignment_batched(c, 0.05, sizes=sizes)
    for i, ci in enumerate(cs):
        s = solve_assignment(jnp.asarray(ci), 0.05)
        m = ci.shape[0]
        np.testing.assert_array_equal(
            np.asarray(r.matching)[i, :m], np.asarray(s.matching)
        )
        # padded rows stay unmatched
        assert (np.asarray(r.matching)[i, m:] == -1).all()
        assert float(r.cost[i]) == pytest.approx(float(s.cost), abs=1e-5)
        assert int(r.phases[i]) == int(s.phases)


def test_solve_assignment_batched_full_shape_no_sizes():
    rng = np.random.default_rng(2)
    c = rng.uniform(size=(3, 48, 48)).astype(np.float32)
    r = solve_assignment_batched(c, 0.1)
    for i in range(3):
        s = solve_assignment(jnp.asarray(c[i]), 0.1)
        np.testing.assert_array_equal(
            np.asarray(r.matching)[i], np.asarray(s.matching)
        )


def test_bucketing_utilities():
    assert next_bucket(1) == 16
    assert next_bucket(16) == 16
    assert next_bucket(17) == 32
    # beyond the largest table entry: mint a ceil-pow2 bucket (one shared
    # compiled program per pow2 size) instead of a per-shape exact bucket
    assert next_bucket(5000) == 8192
    assert next_bucket(2049) == 4096
    assert next_bucket(4096) == 4096
    groups = bucket_instances([(20, 20), (30, 10), (100, 100), (31, 9)])
    keys = {g.key for g in groups}
    assert keys == {(32, 32), (32, 16), (128, 128)}
    covered = sorted(i for g in groups for i in g.indices)
    assert covered == [0, 1, 2, 3]
    padded = pad_stack([np.ones((2, 3)), np.ones((1, 2))], (4, 4))
    assert padded.shape == (2, 4, 4)
    assert float(padded.sum()) == 8.0


def test_solve_ot_ragged_roundtrip():
    insts = _ragged_ot_instances(5, 10, 70, seed=5)
    rs = solve_ot_ragged(insts, 0.1)
    for (ci, nui, mui), r in zip(insts, rs):
        s = solve_ot(jnp.asarray(ci), jnp.asarray(nui), jnp.asarray(mui), 0.1)
        assert r["plan"].shape == ci.shape
        assert r["cost"] == pytest.approx(float(s.cost), abs=2e-6)


def test_solve_assignment_ragged_roundtrip():
    rng = np.random.default_rng(9)
    cs = [np.asarray(build_cost_matrix(rng.uniform(size=(m, 2)),
                                       rng.uniform(size=(m, 2)), "euclidean"))
          for m in (18, 33, 64, 40)]
    rs = solve_assignment_ragged(cs, 0.1)
    for ci, r in zip(cs, rs):
        s = solve_assignment(jnp.asarray(ci), 0.1)
        np.testing.assert_array_equal(r["matching"], np.asarray(s.matching))
        assert r["cost"] == pytest.approx(float(s.cost), abs=1e-5)


def test_slack_propose_batched_matches_single():
    """Batched kernel (leading batch dim in the grid) == per-instance kernel,
    bit for bit, including per-instance salts and padded tiles."""
    from repro.kernels import ops
    from repro.kernels import slack_propose as sp

    rng = np.random.default_rng(13)
    b, m, n = 4, 70, 130
    c = rng.integers(0, 6, size=(b, m, n)).astype(np.int32)
    y_b = rng.integers(0, 4, size=(b, m)).astype(np.int32)
    y_a = -rng.integers(0, 4, size=(b, n)).astype(np.int32)
    avail = rng.uniform(size=(b, n)) < 0.6
    salts = rng.integers(0, 10_000, size=b).astype(np.int32)

    bc, bk = ops.slack_propose_batched(
        jnp.asarray(c), jnp.asarray(y_b), jnp.asarray(y_a),
        jnp.asarray(avail), jnp.asarray(salts),
    )
    for i in range(b):
        sc, sk = sp.slack_propose(
            jnp.asarray(c[i]), jnp.asarray(y_b[i]), jnp.asarray(y_a[i]),
            jnp.asarray(avail[i]), int(salts[i]), interpret=True,
        )
        np.testing.assert_array_equal(np.asarray(bc)[i], np.asarray(sc))
        np.testing.assert_array_equal(np.asarray(bk)[i], np.asarray(sk))

    # block-size invariance of the batched accumulator pattern
    bc2, _ = ops.slack_propose_batched(
        jnp.asarray(c), jnp.asarray(y_b), jnp.asarray(y_a),
        jnp.asarray(avail), jnp.asarray(salts), block_m=32, block_n=32,
    )
    np.testing.assert_array_equal(np.asarray(bc), np.asarray(bc2))


def test_ot_service_bucketed_queue():
    """Mixed-size queue: results come back in submission order, grouped into
    shape buckets, and match one-at-a-time unbatched solves."""
    from repro.serve.engine import OTService

    rng = np.random.default_rng(1)
    svc = OTService(eps=0.1)
    refs = []
    for m in (20, 60, 20, 90):
        x = rng.uniform(size=(m, 2)).astype(np.float32)
        y = rng.uniform(size=(m, 2)).astype(np.float32)
        ticket = svc.submit(x, y)
        assert ticket == len(refs)
        c = build_cost_matrix(jnp.asarray(x), jnp.asarray(y), "euclidean")
        refs.append(float(solve_assignment(c, 0.1).cost) / m)
    # one general-OT request rides in the same dispatch
    x = rng.uniform(size=(25, 2)).astype(np.float32)
    y = rng.uniform(size=(35, 2)).astype(np.float32)
    nu = rng.dirichlet(np.ones(25)).astype(np.float32)
    mu = rng.dirichlet(np.ones(35)).astype(np.float32)
    svc.submit(x, y, nu=nu, mu=mu)

    res = svc.run_batch()
    assert len(res) == 5
    assert svc.queue == []
    for i, ref in enumerate(refs):
        assert res[i]["cost"] == pytest.approx(ref, abs=1e-5)
    assert res[0]["bucket"] == (32, 32) and res[0]["batch_size"] == 2
    assert res[3]["bucket"] == (128, 128)
    assert res[4]["plan"].shape == (25, 35)
    c = build_cost_matrix(jnp.asarray(x), jnp.asarray(y), "euclidean")
    s = solve_ot(c, jnp.asarray(nu), jnp.asarray(mu), 0.1)
    assert res[4]["cost"] == pytest.approx(float(s.cost), abs=2e-6)


def test_ot_service_endpoint():
    from repro.serve.engine import OTService

    rng = np.random.default_rng(1)
    svc = OTService(eps=0.1)
    out = svc.distance(rng.uniform(size=(64, 2)).astype(np.float32),
                       rng.uniform(size=(64, 2)).astype(np.float32))
    assert out["cost"] >= out["dual_lower_bound"] - 0.35  # weak duality + eps
    assert len(np.unique(out["matching"])) == 64
