"""The certified front door on the input families the benchmark serves.

Every case is one ``core.api.solve`` call under
``DispatchPolicy(mode=..., guaranteed=True)``, held to its certificate and
to an exact solver (``core/exact.py``: JV for assignment, the HiGHS LP for
OT): the duals are eps-feasible, the a-posteriori gap is within the paper's
bound, the cost is within that bound of the optimum, and the plan is sound.
Each family comes at two sizes, a power of two and one that pads to the
next shape bucket, at eps 0.1 and 0.02, under the lockstep and the
compacting driver. Each instance is drawn from its own fixed seed."""
import zlib

import numpy as np
import pytest

from repro.core.api import ASSIGNMENT, OT, DispatchPolicy, solve
from repro.core.exact import exact_assignment_cost, exact_ot_cost

WANT = ("cost", "duals", "plan_sparse")
EPS = (0.1, 0.02)
MODES = ("lockstep", "compact")
# the benchmark's plan_err limit: marginal error over the largest mass
PLAN_TOL = 1e-3


def _rng(family: str, size) -> np.random.Generator:
    return np.random.default_rng([zlib.crc32(family.encode()),
                                  *np.atleast_1d(size)])


def _euclid(x, y) -> np.ndarray:
    return np.sqrt(((x[:, None, :] - y[None, :, :]) ** 2).sum(-1))


# -- assignment families: (m, n) float costs --------------------------------

def _uniform_euclid(n, rng):
    """The paper's section 5 input: uniform points in the unit square."""
    return _euclid(rng.uniform(size=(n, 2)), rng.uniform(size=(n, 2)))


def _clustered_ties(n, rng):
    """Points at 3 sites, so most costs tie exactly."""
    sites = rng.uniform(size=(3, 2))
    return _euclid(sites[rng.integers(0, 3, n)], sites[rng.integers(0, 3, n)])


def _rectangular(n, rng):
    """n // 2 rows against n columns."""
    return _euclid(rng.uniform(size=(n // 2, 2)), rng.uniform(size=(n, 2)))


# -- OT families: (c, nu, mu) -------------------------------------------------

def _whitenoise_grid(side, rng):
    """DOTmark WhiteNoise: i.i.d. uniform pixels normalised to mass 1 on a
    side x side grid scaled to the unit square, squared-Euclidean cost."""
    idx = np.arange(side * side)
    p = np.stack([idx // side, idx % side], 1) / (side - 1)
    c = ((p[:, None, :] - p[None, :, :]) ** 2).sum(-1)
    img = rng.uniform(size=(2, side * side))
    img /= img.sum(1, keepdims=True)
    return c, img[0], img[1]


def _zipf(n, rng):
    w = 1.0 / rng.permutation(np.arange(1, n + 1))
    return w / w.sum()


def _zipf_marginals(n, rng):
    """Masses proportional to 1 / rank on random points."""
    c = _euclid(rng.uniform(size=(n, 2)), rng.uniform(size=(n, 2)))
    return c, _zipf(n, rng), _zipf(n, rng)


def _half_zero(n, rng):
    w = rng.uniform(size=n)
    w[rng.permutation(n)[: n // 2]] = 0.0
    return w / w.sum()


def _sparse_support(n, rng):
    """Half the masses exactly zero on each side."""
    c = _euclid(rng.uniform(size=(n, 2)), rng.uniform(size=(n, 2)))
    return c, _half_zero(n, rng), _half_zero(n, rng)


def _token_logits(t, e, rng):
    """Router logits skewed to expert 0: +5 on it, N(0, 1) noise on all."""
    logits = rng.standard_normal((t, e))
    logits[:, 0] += 5.0
    return logits


def _token_expert(logits):
    """(t, e) logits as t tokens of mass 1/t against e experts of equal
    capacity 1/e."""
    t, e = logits.shape
    return logits.max() - logits, np.full(t, 1.0 / t), np.full(e, 1.0 / e)


ASSIGNMENT_FAMILIES = {
    "uniform_euclid": (_uniform_euclid, (16, 23)),
    "clustered_ties": (_clustered_ties, (16, 23)),
    "rectangular": (_rectangular, (16, 23)),
}
OT_FAMILIES = {
    "whitenoise_grid": (_whitenoise_grid, (4, 5)),
    "zipf_marginals": (_zipf_marginals, (16, 23)),
    "sparse_support": (_sparse_support, (16, 23)),
}
TOKEN_EXPERT_SIZES = ((16, 4), (23, 5))


def _cases(families):
    return [pytest.param(name, size, eps, mode,
                         id=f"{name}-{size}-eps{eps}-{mode}")
            for name, (_, sizes) in families.items() for size in sizes
            for eps in EPS for mode in MODES]


def _solve_certified(spec, inst, eps, mode, exact):
    """One certified solve; asserts the certificate and the exact bound."""
    sol = solve(spec, [inst], eps, DispatchPolicy(mode=mode, guaranteed=True),
                want=WANT)[0]
    bound = sol.additive_gap_bound()
    assert sol.dual_feasible()
    assert sol.additive_gap() <= bound, (sol.additive_gap(), bound)
    # a primal answer may undercut the optimum only by float rounding
    assert -1e-4 * max(1.0, abs(exact)) <= sol.cost - exact <= bound, (
        sol.cost, exact, bound)
    return sol


@pytest.mark.parametrize("family,size,eps,mode", _cases(ASSIGNMENT_FAMILIES))
def test_assignment_family_certified(family, size, eps, mode):
    make, _ = ASSIGNMENT_FAMILIES[family]
    c = make(size, _rng(family, size)).astype(np.float32)
    sol = _solve_certified(ASSIGNMENT, c, eps, mode, exact_assignment_cost(c))
    plan = sol.plan_sparse()
    m = c.shape[0]
    # every row matched, to a distinct column
    np.testing.assert_array_equal(np.sort(plan.rows), np.arange(m))
    assert len(np.unique(plan.cols)) == m


def _check_ot_plan(sol, nu, mu):
    plan = sol.plan_sparse().to_dense().astype(np.float64)
    tol = PLAN_TOL * max(nu.max(), mu.max())
    assert plan.min() >= -tol
    np.testing.assert_allclose(plan.sum(1), nu, rtol=0, atol=tol)
    np.testing.assert_allclose(plan.sum(0), mu, rtol=0, atol=tol)
    return plan


def _solve_ot_case(c, nu, mu, eps, mode):
    c, nu, mu = (a.astype(np.float32) for a in (c, nu, mu))
    sol = _solve_certified(OT, (c, nu, mu), eps, mode,
                           exact_ot_cost(c, nu, mu))
    return _check_ot_plan(sol, nu, mu)


@pytest.mark.parametrize("family,size,eps,mode", _cases(OT_FAMILIES))
def test_ot_family_certified(family, size, eps, mode):
    make, _ = OT_FAMILIES[family]
    _solve_ot_case(*make(size, _rng(family, size)), eps, mode)


@pytest.mark.parametrize("eps", EPS)
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("t,e", TOKEN_EXPERT_SIZES)
def test_token_expert_routing_certified(t, e, mode, eps):
    """Balanced token -> expert routing through the OT spec: the skew that
    sends an argmax router's tokens to expert 0 leaves every expert within
    its capacity."""
    logits = _token_logits(t, e, _rng("token_expert", (t, e)))
    assert np.mean(logits.argmax(1) == 0) > 0.9
    plan = _solve_ot_case(*_token_expert(logits), eps, mode)
    assert plan.sum(0).max() <= 1.0 / e + 1e-3
