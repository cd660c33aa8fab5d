"""Solver portfolio: Sinkhorn-as-a-spec, the measured auto-policy, and
the hybrid warm start.

Certificate parity is the load-bearing contract: a Solution produced by
ANY portfolio solver must certify the same additive-eps bound through
the same ``additive_gap()``/``dual_feasible()`` surface the push-relabel
solver uses. The hybrid solver additionally must be exactly as feasible
as a cold-start push-relabel solve (its warm initial state satisfies
every paper invariant by construction — ``round_duals`` clips into the
invariant polytope, so a garbage warm start can cost phases but never
correctness).

Float tolerances, documented once here: the Pallas row kernel and the
pure-jnp f-update evaluate the same online logsumexp with different
reduction orders; on f32 that is reassociation-level noise, bounded in
practice well under 1e-5 absolute on O(1)-magnitude potentials. The
chunked-vs-one-shot resumability contract, by contrast, is BIT-exact
(same programs, same order, only the dispatch boundary moves).
"""
import json

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.api import OT, DispatchPolicy, dispatch, solve
from repro.core.compaction import solve_compacting, spec_fns
from repro.core.feasibility import check_ot_invariants
from repro.core.problem import eps_array
from repro.portfolio import (
    SINKHORN,
    SINKHORN_KERNEL,
    WARM_OT,
    CostModel,
    dispatch_hybrid,
    fit,
    round_duals,
    set_model,
)
from repro.portfolio.hybrid import _COARSE_EPS, _WARM_ITERS
from repro.portfolio.sinkhorn_spec import (
    SinkhornState,
    _row_update_jnp,
    run_sinkhorn_phases,
    sinkhorn_schedule,
)


@pytest.fixture(scope="module", autouse=True)
def _release_compiler_state():
    # This module compiles three solver families' worth of programs; on
    # single-core CI the XLA compiler segfaults partway into the NEXT
    # test module once that much compiler state has accumulated in the
    # process. Dropping the executable caches when the module finishes
    # keeps the suite under the cliff; later modules just recompile.
    yield
    jax.clear_caches()


def _ot_batch(b, m, n, seed=0):
    rng = np.random.default_rng(seed)
    c = rng.uniform(0.1, 1.0, (b, m, n)).astype(np.float32)
    nu = rng.uniform(0.5, 1.5, (b, m)).astype(np.float32)
    nu /= nu.sum(1, keepdims=True)
    mu = rng.uniform(0.5, 1.5, (b, n)).astype(np.float32)
    mu /= mu.sum(1, keepdims=True)
    return {"c": c, "nu": nu, "mu": mu}


class _Events:
    """Minimal obs stand-in: records every event kind."""

    def __init__(self):
        self.kinds = []

    def event(self, kind, **attrs):
        self.kinds.append((kind, attrs))


# ---------------------------------------------------------------------------
# cross-solver certificate parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("eps", [0.3, 0.1])
@pytest.mark.parametrize("mn", [8, 16])
@pytest.mark.parametrize("solver", ["pushrelabel", "sinkhorn", "hybrid"])
def test_certificate_grid(solver, mn, eps):
    inputs = _ot_batch(2, mn, mn, seed=mn)
    pol = DispatchPolicy(mode="compact", solver=solver, guaranteed=True)
    sols = solve(OT, inputs, eps, pol, want=("cost", "duals", "stats"))
    assert sols.stats.solver == solver
    for i in range(2):
        s = sols[i]
        assert bool(s.dual_feasible())
        assert float(s.additive_gap()) <= float(s.additive_gap_bound()) \
            + 1e-6


def test_sinkhorn_marginals_exact():
    # AWR Algorithm 2 rounding: the returned plan sits ON the transport
    # polytope (marginals exact to f32), not merely near it
    inputs = _ot_batch(2, 12, 12, seed=5)
    r, _ = solve_compacting(SINKHORN, inputs, 0.3)
    plan = np.asarray(r.plan, np.float64)
    np.testing.assert_allclose(plan.sum(2), inputs["nu"], atol=2e-6)
    np.testing.assert_allclose(plan.sum(1), inputs["mu"], atol=2e-6)


def test_sinkhorn_padded_lane_regression():
    # padded rows/cols (ragged sizes) once produced -inf potentials via a
    # subnormal log floor that FTZ backends flush to zero -> NaN cost
    b, mb, nb, m, n = 1, 16, 16, 10, 12
    rng = np.random.default_rng(2)
    c = np.zeros((b, mb, nb), np.float32)
    c[0, :m, :n] = rng.uniform(0.1, 1.0, (m, n))
    nu = np.zeros((b, mb), np.float32)
    nu[0, :m] = 1.0 / m
    mu = np.zeros((b, nb), np.float32)
    mu[0, :n] = 1.0 / n
    r, _ = solve_compacting(SINKHORN, {"c": c, "nu": nu, "mu": mu}, 0.3,
                            sizes=np.array([[m, n]], np.int32))
    assert np.isfinite(np.asarray(r.cost)).all()
    plan = np.asarray(r.plan[0], np.float64)
    assert plan[m:, :].sum() + plan[:, n:].sum() < 1e-6
    np.testing.assert_allclose(plan.sum(1)[:m], nu[0, :m], atol=2e-6)


# ---------------------------------------------------------------------------
# resumability + kernel parity
# ---------------------------------------------------------------------------


def test_sinkhorn_chunk_resumable_bit_identical():
    inputs = _ot_batch(3, 12, 12, seed=7)
    r_small, _ = solve_compacting(SINKHORN, inputs, 0.3, k=3)
    r_big, _ = solve_compacting(SINKHORN, inputs, 0.3, k=512)
    for f, a, b in zip(r_small._fields, r_small, r_big):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"field {f}")


def test_kernel_row_update_parity():
    # Pallas flash-style row update vs pure jnp: same online logsumexp,
    # different reduction order -> reassociation-level f32 noise only
    rng = np.random.default_rng(11)
    m, n = 24, 40
    c_hat = rng.uniform(0.0, 1.0, (m, n)).astype(np.float32)
    g = rng.normal(0.0, 0.2, n).astype(np.float32)
    log_nu = np.full(m, -np.log(m), np.float32)
    reg = jnp.float32(0.05)
    from repro.kernels import ops

    ref = _row_update_jnp(jnp.asarray(c_hat), jnp.asarray(g),
                          jnp.asarray(log_nu), reg)
    out = ops.sinkhorn_row_update(jnp.asarray(c_hat), jnp.asarray(g),
                                  jnp.asarray(log_nu), reg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_kernel_spec_matches_stepped_spec():
    inputs = _ot_batch(2, 16, 16, seed=9)
    r_jnp, _ = solve_compacting(SINKHORN, inputs, 0.3)
    r_krn, _ = solve_compacting(SINKHORN_KERNEL, inputs, 0.3)
    np.testing.assert_allclose(np.asarray(r_krn.cost),
                               np.asarray(r_jnp.cost), atol=1e-5)
    np.testing.assert_allclose(np.asarray(r_krn.y_b),
                               np.asarray(r_jnp.y_b), atol=1e-5)


def test_fused_policy_resolves_kernel_spec():
    from repro.core.problem import fused_variant

    assert fused_variant(SINKHORN) is SINKHORN_KERNEL
    assert SINKHORN_KERNEL.stepped is SINKHORN


# ---------------------------------------------------------------------------
# schedule (host-f64 thresholds)
# ---------------------------------------------------------------------------


def test_schedule_host_f64():
    eps = np.asarray([0.3, 0.1])
    reg, tol, cap = sinkhorn_schedule(eps, np.array([16, 16]),
                                      np.array([16, 16]))
    assert reg.dtype == np.float64 and tol.dtype == np.float64
    assert cap.dtype == np.int32
    np.testing.assert_allclose(tol, eps / 8.0)
    np.testing.assert_allclose(reg, eps / (4.0 * np.log(16.0)))
    # tiny eps must clip, not overflow, the int32 cap
    _, _, cap2 = sinkhorn_schedule(np.asarray([1e-6]), np.array([16]),
                                   np.array([16]))
    assert cap2[0] == np.iinfo(np.int32).max


# ---------------------------------------------------------------------------
# hybrid: warm-start feasibility == cold-start feasibility
# ---------------------------------------------------------------------------


def _warm_initial_state(inputs, eps, seed=1):
    b = inputs["c"].shape[0]
    eps_coarse = np.maximum(np.full(b, eps), _COARSE_EPS)
    _, st1 = solve_compacting(SINKHORN, inputs, eps_coarse,
                              keep_state=True, max_iters=_WARM_ITERS)
    warm = st1.final_state
    eps_int = jnp.asarray(eps_array(eps, b, False), jnp.float32)
    y_b0 = round_duals(jnp.asarray(inputs["c"]), jnp.asarray(inputs["mu"]),
                       warm.f, warm.g, eps_int)
    p = WARM_OT.prepare(WARM_OT.canonicalize(inputs), eps,
                        y_b0=np.asarray(y_b0))
    prologue, init, _, _, _ = spec_fns(WARM_OT, 1)
    ops = {kk: jnp.asarray(v) for kk, v in p.ops.items()}
    data, ctx = prologue(ops)
    ctx = {**ctx, **{kk: ops[kk] for kk in WARM_OT.ctx_ops}}
    return data, ctx, init(data, ctx), p


def test_hybrid_warm_state_invariants():
    inputs = _ot_batch(2, 12, 12, seed=1)
    data, ctx, state0, p = _warm_initial_state(inputs, 0.1)
    for i in range(2):
        one = jax.tree_util.tree_map(lambda a: a[i], state0)
        rep = check_ot_invariants(
            np.asarray(data["c_int"])[i], one,
            np.asarray(ctx["s_int"])[i], np.asarray(ctx["d_int"])[i],
            float(p.eps_arr[i]))
        assert all(rep.values()), rep


def test_hybrid_feasibility_parity_with_cold_start():
    inputs = _ot_batch(2, 12, 12, seed=3)
    eps = 0.1
    pol_h = DispatchPolicy(mode="compact", solver="hybrid",
                           guaranteed=True)
    pol_c = DispatchPolicy(mode="compact", solver="pushrelabel",
                           guaranteed=True)
    sh = solve(OT, inputs, eps, pol_h, want=("cost", "duals", "stats"))
    sc = solve(OT, inputs, eps, pol_c, want=("cost", "duals", "stats"))
    for i in range(2):
        # identical certificate surface: both feasible, both within the
        # same bound (plans may differ — both are eps-optimal)
        assert bool(sh[i].dual_feasible()) == bool(sc[i].dual_feasible()) \
            == True  # noqa: E712
        bound = float(sc[i].additive_gap_bound())
        assert float(sh[i].additive_gap()) <= bound + 1e-6
        assert float(sc[i].additive_gap()) <= bound + 1e-6


def test_hybrid_stats_fold_stage1_dispatches():
    inputs = _ot_batch(2, 12, 12, seed=4)
    r, stats = dispatch_hybrid(inputs, 0.1,
                               policy=DispatchPolicy(mode="compact"))
    # at least one Sinkhorn chunk + one push-relabel chunk
    assert stats.dispatches >= 2
    assert np.isfinite(np.asarray(r.cost)).all()


def test_warm_ot_defaults_to_cold_start():
    # y_b0 omitted -> WARM_OT degrades to plain OT, bit for bit
    inputs = _ot_batch(2, 10, 10, seed=6)
    r_warm, _ = solve_compacting(WARM_OT, inputs, 0.2)
    r_cold, _ = solve_compacting(OT, inputs, 0.2)
    np.testing.assert_array_equal(np.asarray(r_warm.cost),
                                  np.asarray(r_cold.cost))
    np.testing.assert_array_equal(np.asarray(r_warm.y_b),
                                  np.asarray(r_cold.y_b))


# ---------------------------------------------------------------------------
# cost model + auto policy
# ---------------------------------------------------------------------------


def _toy_model(cheap="sinkhorn"):
    rows = []
    for solver in ("pushrelabel", "sinkhorn", "hybrid"):
        rows.append({"solver": solver, "n": 16, "eps": 0.1,
                     "per_instance_s": 0.001 if solver == cheap else 0.5})
    return fit(rows, mode="interpret", backend="cpu")


def test_costmodel_roundtrip(tmp_path):
    model = _toy_model()
    path = str(tmp_path / "cm.json")
    model.save(path)
    loaded = CostModel.load(path)
    assert loaded == model
    payload = json.loads(open(path).read())
    assert payload["mode"] == "interpret"  # honest-labeling survives disk
    # log-nearest snapping: n=20 -> bucket 16, eps=0.12 -> band 0.1
    assert loaded.predict("sinkhorn", 20, 0.12) == \
        loaded.predict("sinkhorn", 16, 0.1)
    assert loaded.choose(16, 0.1)[0] == "sinkhorn"


def test_auto_bit_identical_to_named_choice():
    inputs = _ot_batch(2, 14, 14, seed=8)
    set_model(_toy_model(cheap="sinkhorn"))
    try:
        sa = solve(OT, inputs, 0.1,
                   DispatchPolicy(mode="compact", solver="auto"),
                   want=("cost", "duals", "stats"))
        sn = solve(OT, inputs, 0.1,
                   DispatchPolicy(mode="compact", solver="sinkhorn"),
                   want=("cost", "duals", "stats"))
        assert sa.stats.solver == "sinkhorn"
        assert sa.stats.predicted_s is not None
        for i in range(2):
            np.testing.assert_array_equal(np.asarray(sa[i].cost),
                                          np.asarray(sn[i].cost))
    finally:
        set_model(None)


def test_auto_without_model_falls_back_to_pushrelabel():
    set_model(CostModel(mode="interpret", backend="cpu", entries={}))
    try:
        inputs = _ot_batch(1, 8, 8, seed=10)
        s = solve(OT, inputs, 0.3,
                  DispatchPolicy(mode="compact", solver="auto"),
                  want=("cost", "stats"))
        assert s.stats.solver == "pushrelabel"
    finally:
        set_model(None)


def test_assignment_ignores_solver_knob():
    from repro.core.api import ASSIGNMENT

    rng = np.random.default_rng(12)
    c = rng.uniform(0.1, 1.0, (2, 8, 8)).astype(np.float32)
    s = solve(ASSIGNMENT, {"c": c}, 0.3,
              DispatchPolicy(mode="compact", solver="sinkhorn"),
              want=("cost", "stats"))
    assert s.stats.solver == "pushrelabel"


def test_policy_rejects_unknown_solver():
    with pytest.raises(ValueError, match="unknown solver"):
        DispatchPolicy(solver="simplex")


def test_solver_choice_obs_event_and_stats_surface():
    inputs = _ot_batch(2, 10, 10, seed=13)
    obs = _Events()
    _, stats = dispatch(OT, inputs, 0.3,
                        policy=DispatchPolicy(mode="compact",
                                              solver="sinkhorn"),
                        obs=obs)
    # the choice rides the stats; the obs sees the driver's chunks
    kinds = {k for k, _ in obs.kinds}
    assert kinds == {"chunk"}
    assert stats.solver == "sinkhorn"
    assert stats.solve_s > 0
    # SolveStats surface carries the portfolio fields through as_dict
    from repro.core.solution import SolveStats

    d = SolveStats.from_driver(stats, mode="compact", batch=2,
                               solver="sinkhorn",
                               predicted_s=0.5).as_dict()
    assert d["solver"] == "sinkhorn"
    assert d["predicted_s"] == 0.5
    assert d["actual_s"] == stats.solve_s


# ---------------------------------------------------------------------------
# serving layers
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("solver", ["sinkhorn", "hybrid"])
def test_otservice_portfolio_end_to_end(solver):
    from repro.serve.engine import OTService

    rng = np.random.default_rng(14)
    svc = OTService(eps=0.3, compact=True, solver=solver,
                    want=("cost", "duals", "stats"))
    for _ in range(2):
        x = rng.normal(size=(10, 2))
        y = rng.normal(size=(12, 2))
        nu = np.abs(rng.normal(size=10)) + 0.1
        mu = np.abs(rng.normal(size=12)) + 0.1
        svc.submit(x, y, nu=nu / nu.sum(), mu=mu / mu.sum())
    for s in svc.run_batch():
        assert s.stats.solver == solver
        assert bool(s.dual_feasible())
        assert float(s.additive_gap()) <= float(s.additive_gap_bound()) \
            + 1e-6


def test_scheduler_portfolio_end_to_end():
    from repro.serve.scheduler import AsyncOTScheduler

    rng = np.random.default_rng(15)
    sched = AsyncOTScheduler(eps=0.3, solver="sinkhorn",
                             want=("cost", "duals", "stats"),
                             linger_ms=5.0)
    try:
        futs = []
        for _ in range(2):
            x = rng.normal(size=(8, 2))
            y = rng.normal(size=(8, 2))
            nu = np.abs(rng.normal(size=8)) + 0.1
            mu = np.abs(rng.normal(size=8)) + 0.1
            futs.append(sched.submit(x, y, nu=nu / nu.sum(),
                                     mu=mu / mu.sum()))
        for f in futs:
            s = f.result(timeout=120)
            assert s.stats.solver == "sinkhorn"
            assert float(s.additive_gap()) <= \
                float(s.additive_gap_bound()) + 1e-6
    finally:
        sched.close()


# ---------------------------------------------------------------------------
# stepped-core unit: the run_phases loop honors caps and chunk budgets
# ---------------------------------------------------------------------------


def test_run_phases_respects_k_and_cap():
    m = n = 8
    rng = np.random.default_rng(16)
    c_hat = jnp.asarray(rng.uniform(0, 1, (m, n)), jnp.float32)
    log_nu = jnp.full((m,), -np.log(m), jnp.float32)
    log_mu = jnp.full((n,), -np.log(n), jnp.float32)
    nu_hat = jnp.full((m,), 1.0 / m, jnp.float32)
    st = SinkhornState(f=jnp.zeros(m), g=jnp.zeros(n),
                       err=jnp.asarray(jnp.inf, jnp.float32),
                       phases=jnp.zeros((), jnp.int32))
    out = run_sinkhorn_phases(c_hat, log_nu, log_mu, nu_hat,
                              jnp.float32(0.05), jnp.float32(1e-9),
                              jnp.int32(1000), st, 4)
    assert int(out.phases) == 4           # chunk budget
    out2 = run_sinkhorn_phases(c_hat, log_nu, log_mu, nu_hat,
                               jnp.float32(0.05), jnp.float32(1e-9),
                               jnp.int32(6), out, 100)
    assert int(out2.phases) == 6          # AWR cap wins over k
