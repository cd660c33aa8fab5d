"""Clustered OT solver: accuracy vs LP, equivalence-class vs explicit copies,
marginal exactness, Lemma 4.1 invariants."""
import numpy as np
import jax.numpy as jnp
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.transport import solve_ot, northwest_corner
from repro.core.copies import solve_ot_via_copies
from repro.core.exact import exact_ot_cost
from repro.core.costs import build_cost_matrix
from repro.core.feasibility import check_ot_invariants


def _instance(n, seed=0, na=None):
    rng = np.random.default_rng(seed)
    na = na or n
    x = rng.uniform(size=(n, 2))
    y = rng.uniform(size=(na, 2))
    c = np.asarray(build_cost_matrix(x, y, "euclidean"))
    nu = rng.dirichlet(np.ones(n))
    mu = rng.dirichlet(np.ones(na))
    return c, nu, mu


@pytest.mark.parametrize("n,eps", [(10, 0.1), (40, 0.1), (40, 0.03), (80, 0.05)])
def test_additive_bound_vs_lp(n, eps):
    c, nu, mu = _instance(n, seed=n)
    opt = exact_ot_cost(c, nu, mu)
    r = solve_ot(jnp.asarray(c), jnp.asarray(nu), jnp.asarray(mu), eps)
    assert float(r.cost) <= opt + 3 * eps * c.max() + 1e-4


@pytest.mark.parametrize("n", [10, 50])
def test_exact_marginals(n):
    c, nu, mu = _instance(n, seed=n + 1)
    r = solve_ot(jnp.asarray(c), jnp.asarray(nu), jnp.asarray(mu), 0.05)
    p = np.asarray(r.plan)
    assert (p >= -1e-9).all()
    np.testing.assert_allclose(p.sum(1), nu, atol=2e-6)
    np.testing.assert_allclose(p.sum(0), mu, atol=2e-6)


def test_matches_explicit_copies_reduction():
    """The clustered solver and the literal Section-4 copies reduction must
    both land within the same additive envelope of the LP optimum."""
    c, nu, mu = _instance(12, seed=5)
    eps, theta = 0.1, 160.0
    opt = exact_ot_cost(c, nu, mu)
    r = solve_ot(
        jnp.asarray(c), jnp.asarray(nu), jnp.asarray(mu), eps, theta=theta
    )
    plan_cp, cost_cp, _, _, _ = solve_ot_via_copies(c, nu, mu, eps, theta)
    env = 3 * eps * c.max() + 2 * 12 / theta * c.max()
    assert float(r.cost) <= opt + env + 1e-4
    assert cost_cp <= opt + env + 1e-4


@pytest.mark.parametrize("eps", [0.1, 0.03])
def test_ot_invariants_at_termination(eps):
    c, nu, mu = _instance(30, seed=23)
    r = solve_ot(jnp.asarray(c), jnp.asarray(nu), jnp.asarray(mu), eps)
    scale = c.max()
    c_int = np.floor(c / scale / eps).astype(np.int32)
    checks = check_ot_invariants(c_int, r.state, r.s_int, r.d_int, eps)
    assert all(checks.values()), checks


def test_unbalanced_supports():
    c, nu, mu = _instance(20, seed=31, na=35)
    opt = exact_ot_cost(c, nu, mu)
    r = solve_ot(jnp.asarray(c), jnp.asarray(nu), jnp.asarray(mu), 0.05)
    assert float(r.cost) <= opt + 3 * 0.05 * c.max() + 1e-4
    np.testing.assert_allclose(np.asarray(r.plan).sum(1), nu, atol=2e-6)
    np.testing.assert_allclose(np.asarray(r.plan).sum(0), mu, atol=2e-6)


def test_assignment_special_case_through_ot():
    """Uniform masses 1/n: OT == assignment/n."""
    n = 25
    c, _, _ = _instance(n, seed=41)
    u = np.full(n, 1.0 / n)
    opt = exact_ot_cost(c, u, u)
    r = solve_ot(jnp.asarray(c), jnp.asarray(u), jnp.asarray(u), 0.05)
    assert float(r.cost) <= opt + 3 * 0.05 * c.max() + 1e-4


def test_northwest_corner_marginals():
    rng = np.random.default_rng(0)
    r = rng.dirichlet(np.ones(17))
    c = rng.dirichlet(np.ones(9))
    p = np.asarray(northwest_corner(jnp.asarray(r), jnp.asarray(c)))
    np.testing.assert_allclose(p.sum(1), r, atol=1e-6)
    np.testing.assert_allclose(p.sum(0), c, atol=1e-6)
    assert (p >= -1e-9).all()


@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(3, 16),
    eps=st.sampled_from([0.2, 0.08]),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_random_ot(n, eps, seed):
    rng = np.random.default_rng(seed)
    c = rng.uniform(size=(n, n)).astype(np.float32)
    nu = rng.dirichlet(np.ones(n))
    mu = rng.dirichlet(np.ones(n))
    opt = exact_ot_cost(c, nu, mu)
    r = solve_ot(jnp.asarray(c), jnp.asarray(nu), jnp.asarray(mu), eps)
    assert float(r.cost) <= opt + 3 * eps * c.max() + 1e-4
    p = np.asarray(r.plan)
    np.testing.assert_allclose(p.sum(1), nu, atol=3e-6)
    np.testing.assert_allclose(p.sum(0), mu, atol=3e-6)
    scale = c.max()
    c_int = np.floor(c / scale / eps).astype(np.int32)
    checks = check_ot_invariants(c_int, r.state, r.s_int, r.d_int, eps)
    assert all(checks.values()), checks


# --------------------------------------------------------------------------
# The round log: ``_phase`` logs each round's grants in an (R, nb) buffer and
# adds them to the flows once per block of at most R rounds. The oracle is
# the phase as it was with a dense (nb, na) ``granted`` matrix in the round
# carry; every OT path must reproduce it bit for bit.
# --------------------------------------------------------------------------

import jax
import jax.extend.core as jex

from repro.core import transport as T

R = T._LOG_ROUNDS


def _dense_phase(c_int, s, max_rounds):
    nb, na = c_int.shape
    free_b0, free_a0 = s.free_b, s.free_a
    m_hi = jnp.sum(s.f_hi, axis=0)
    cap0 = jnp.where(s.ya_hi == 0, s.free_a, 0) + m_hi
    granted0 = jnp.zeros((nb, na), jnp.int32)

    def cond(c):
        rem_b, cap_a, granted, rounds, done = c
        return (~done) & (rounds < max_rounds)

    def body(c):
        rem_b, cap_a, granted, rounds, _ = c
        salt = s.phases * jnp.int32(7919) + rounds
        tgt_safe, grant, any_prop = T._grant_round(
            c_int, s.y_b, s.ya_hi, rem_b, cap_a, salt
        )
        rows = jnp.arange(nb, dtype=jnp.int32)
        granted = granted.at[rows, jnp.clip(tgt_safe, 0, na - 1)].add(
            jnp.where(tgt_safe < na, grant, 0)
        )
        cap_a = cap_a.at[tgt_safe].add(-grant, mode="drop")
        rem_b = rem_b - grant
        return (rem_b, cap_a, granted, rounds + 1, ~any_prop)

    rem_b, cap_a, granted, rounds, _ = jax.lax.while_loop(
        cond, body, (free_b0, cap0, granted0, jnp.int32(0), jnp.bool_(False))
    )

    g_a = jnp.sum(granted, axis=0)
    use_free = jnp.minimum(g_a, jnp.where(s.ya_hi == 0, free_a0, 0))
    disp = g_a - use_free
    suffix_excl = jnp.cumsum(s.f_hi[::-1], axis=0)[::-1] - s.f_hi
    take = jnp.clip(disp[None, :] - suffix_excl, 0, s.f_hi)
    f_hi = s.f_hi - take
    freed_b = jnp.sum(take, axis=1)
    free_a = free_a0 - use_free
    hi_left = jnp.where(s.ya_hi == 0, free_a, 0) + jnp.sum(f_hi, axis=0)
    collapse = (hi_left == 0) & (g_a > 0)
    ya_hi = jnp.where(collapse, s.ya_hi - 1, s.ya_hi)
    f_hi_new = jnp.where(collapse[None, :], s.f_lo + granted, f_hi)
    f_lo_new = jnp.where(collapse[None, :], 0, s.f_lo + granted)
    y_b = s.y_b + ((free_b0 > 0) & (rem_b > 0)).astype(jnp.int32)
    return T.OTState(
        y_b=y_b, ya_hi=ya_hi, free_b=rem_b + freed_b, free_a=free_a,
        f_hi=f_hi_new, f_lo=f_lo_new, phases=s.phases + 1,
        rounds=s.rounds + rounds,
    )


def _dense_run_phases(c_int, s, threshold, phase_cap, k, max_rounds):
    start = s.phases

    def cond(s):
        return ((jnp.sum(s.free_b) > threshold) & (s.phases < phase_cap)
                & (s.phases - start < k))

    return jax.lax.while_loop(
        cond, lambda s: _dense_phase(c_int, s, max_rounds), s)


_dense_step = jax.jit(_dense_phase, static_argnums=2)
_log_step = jax.jit(T._phase, static_argnums=2)


def _assert_states_equal(a, b, where=""):
    for name, x, y in zip(T.OTState._fields, a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"{where} {name}")


def _oracle_phase_rounds(c_int, s_int, d_int, threshold, phase_cap,
                         max_rounds, log_check=False):
    """Phase by phase with the dense oracle: its final state and the rounds
    each phase took. With ``log_check`` every phase of ``_phase`` is
    compared with the oracle's from the same state (the trajectory)."""
    s = T.init_ot_state(s_int, d_int)
    per_phase = []
    while int(jnp.sum(s.free_b)) > threshold and int(s.phases) < phase_cap:
        nxt = _dense_step(c_int, s, max_rounds)
        if log_check:
            _assert_states_equal(_log_step(c_int, s, max_rounds), nxt,
                                 f"phase {int(s.phases)}")
        per_phase.append(int(nxt.rounds) - int(s.rounds))
        s = nxt
    return s, per_phase


def _rounded(nb, na, eps, seed):
    """A random instance through the OT prologue, with its exact threshold."""
    rng = np.random.default_rng(seed)
    c = rng.uniform(size=(nb, na)).astype(np.float32)
    nu = rng.dirichlet(np.ones(nb)).astype(np.float32)
    mu = rng.dirichlet(np.ones(na)).astype(np.float32)
    theta = 4.0 * max(nb, na) / eps
    c_int, s_int, d_int, scale = T.ot_prologue(c, nu, mu, theta, eps)
    thr = T.ot_termination_threshold(nu, theta, eps)
    return dict(c=c, nu=nu, mu=mu, theta=theta, eps=eps, c_int=c_int,
                s_int=s_int, d_int=d_int, scale=scale, thr=thr,
                cap=T.ot_phase_cap(eps), max_rounds=nb + na + 2)


def _check_one_shot(inst, want_overflow):
    d = inst
    fin, per_phase = _oracle_phase_rounds(
        d["c_int"], d["s_int"], d["d_int"], d["thr"], d["cap"],
        d["max_rounds"])
    assert (max(per_phase) > R) == want_overflow, per_phase
    got = T.solve_ot_int(d["c_int"], d["s_int"], d["d_int"], d["eps"],
                         d["cap"], d["max_rounds"], d["thr"])
    _assert_states_equal(got, fin, "solve_ot_int")

    res = T.ot_pipeline(jnp.asarray(d["c"]), jnp.asarray(d["nu"]),
                        jnp.asarray(d["mu"]), d["theta"], d["eps"],
                        threshold=d["thr"])
    ref = T.ot_epilogue(d["c"], d["nu"], d["mu"], d["theta"], d["eps"],
                        d["scale"], d["s_int"], d["d_int"], fin)
    for name in ("plan", "cost", "y_b", "y_a"):
        np.testing.assert_array_equal(np.asarray(getattr(res, name)),
                                      np.asarray(getattr(ref, name)),
                                      err_msg=name)


def _check_vmapped(k):
    # Eight lanes of one shape whose phases take from 5 to 44 rounds, so
    # lanes leave the round loop in different blocks.
    nb, na = 3, 100
    lanes = [_rounded(nb, na, eps, seed) for eps, seed in
             [(0.5, 0), (0.5, 2), (0.25, 3), (0.1, 4),
              (0.5, 5), (0.3, 6), (0.05, 7), (0.5, 8)]]
    max_rounds = nb + na + 2
    longest = max(max(_oracle_phase_rounds(
        d["c_int"], d["s_int"], d["d_int"], d["thr"], d["cap"],
        max_rounds)[1]) for d in lanes)
    assert longest > R, longest

    stack = lambda key: jnp.stack([jnp.asarray(d[key]) for d in lanes])
    c_int = stack("c_int")
    thr = jnp.asarray([d["thr"] for d in lanes], jnp.int32)
    cap = jnp.asarray([d["cap"] for d in lanes], jnp.int32)
    new = jax.jit(jax.vmap(lambda c, s, t, p: T.run_ot_phases(
        c, s, t, p, k=k, max_rounds=max_rounds)))
    old = jax.jit(jax.vmap(lambda c, s, t, p: _dense_run_phases(
        c, s, t, p, k, max_rounds)))
    s_new = jax.vmap(T.init_ot_state)(stack("s_int"), stack("d_int"))
    s_old = jax.vmap(T.init_ot_state)(stack("s_int"), stack("d_int"))
    for chunk in range(400):
        s_new = new(c_int, s_new, thr, cap)
        s_old = old(c_int, s_old, thr, cap)
        _assert_states_equal(s_new, s_old, f"chunk {chunk}")
        if bool(jnp.all(jax.vmap(T.ot_converged)(s_old, thr, cap))):
            break
    else:
        raise AssertionError("chunks did not converge")


@pytest.mark.parametrize("case", [
    "one_shot", "one_shot_overflow", "vmap_k1", "vmap_k8",
])
def test_round_log_bit_identical(case):
    """``_phase`` with its round log gives the dense-``granted`` oracle's
    ``OTState`` bit for bit, and ``ot_pipeline`` its plan, cost and duals:
    one-shot on a square instance (phases within one block), one-shot on an
    unbalanced one whose phases run more than R rounds (overflow blocks),
    and vmapped chunks of k = 1 and 8 phases."""
    if case == "one_shot":
        _check_one_shot(_rounded(24, 24, 0.05, 11), want_overflow=False)
    elif case == "one_shot_overflow":
        _check_one_shot(_rounded(2, 160, 0.5, 1), want_overflow=True)
    else:
        _check_vmapped(k=int(case[-1]))


def _whitenoise(side, seed):
    n = side * side
    idx = np.arange(n)
    p = np.stack([idx // side, idx % side], 1) / (side - 1)
    c = ((p[:, None] - p[None]) ** 2).sum(-1).astype(np.float32)
    img = np.random.default_rng(seed).uniform(size=(2, n)).astype(np.float32)
    img /= img.sum(1, keepdims=True)
    return c, img[0], img[1]


@pytest.mark.parametrize("eps", [0.1, 0.02])
def test_round_log_overflow_share_dotmark32(eps):
    """On a DOTmark-WhiteNoise-like 32^2 pair at the guaranteed eps/3, under
    10 % of phases need more than one block of the round log, and every
    phase matches the oracle."""
    c, nu, mu = _whitenoise(32, seed=7)
    e = eps / 3.0
    n = c.shape[0]
    theta = 4.0 * n / e
    c_int, s_int, d_int, _ = T.ot_prologue(c, nu, mu, theta, e)
    _, per_phase = _oracle_phase_rounds(
        c_int, s_int, d_int, T.ot_termination_threshold(nu, theta, e),
        T.ot_phase_cap(e), 2 * n + 2, log_check=True)
    share = float(np.mean(np.asarray(per_phase) > R))
    assert share < 0.10, (share, per_phase)


def _innermost_while_carries(jaxpr):
    """Carried operand avals of every ``while`` that holds no other."""
    found = []

    def subjaxprs(eqn):
        for v in eqn.params.values():
            for x in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(x, jex.ClosedJaxpr):
                    yield x.jaxpr
                elif isinstance(x, jex.Jaxpr):
                    yield x

    def walk(j):
        has_while = False
        for eqn in j.eqns:
            inner = any([walk(sub) for sub in subjaxprs(eqn)])
            if eqn.primitive.name == "while":
                has_while = True
                if not inner:
                    n_consts = (eqn.params["cond_nconsts"]
                                + eqn.params["body_nconsts"])
                    found.append([v.aval for v in eqn.invars[n_consts:]])
            has_while = has_while or inner
        return has_while

    walk(jaxpr)
    return found


def test_round_loop_carries_no_flow_matrix():
    """The vmapped chunk's innermost loops (the phase's round loops) carry
    no (B, nb, na) array: under vmap a carried matrix is selected whole
    every round. The dense oracle's does, which the guard must see."""
    b, nb, na, max_rounds = 4, 16, 24, 42
    c_int = jnp.zeros((b, nb, na), jnp.int32)
    state = jax.vmap(T.init_ot_state)(jnp.ones((b, nb), jnp.int32),
                                      jnp.ones((b, na), jnp.int32))
    thr = jnp.zeros((b,), jnp.int32)
    cap = jnp.full((b,), 8, jnp.int32)

    def carries(run):
        jaxpr = jax.make_jaxpr(jax.vmap(
            lambda c, s, t, p: run(c, s, t, p, 8, max_rounds)))(
                c_int, state, thr, cap)
        return _innermost_while_carries(jaxpr.jaxpr)

    new = carries(T.run_ot_phases)
    assert len(new) == 2, new  # the first block's round loop, the overflow's
    for avals in new:
        assert all(a.shape != (b, nb, na) for a in avals), avals
    old = carries(_dense_run_phases)
    assert any(a.shape == (b, nb, na) for avals in old for a in avals), old
