"""Sinkhorn baseline sanity: feasible-ish plans, log vs kernel agreement,
and the small-reg underflow the paper points out for the kernel variant."""
import numpy as np
import jax.numpy as jnp

from repro.core.sinkhorn import sinkhorn, reg_for_additive_eps
from repro.core.exact import exact_ot_cost
from repro.core.costs import build_cost_matrix


def _instance(n, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.uniform(size=(n, 2))
    y = rng.uniform(size=(n, 2))
    c = np.asarray(build_cost_matrix(x, y, "euclidean"))
    nu = rng.dirichlet(np.ones(n))
    mu = rng.dirichlet(np.ones(n))
    return c, nu, mu


def test_log_domain_converges_and_bounds():
    c, nu, mu = _instance(40, 1)
    r = sinkhorn(jnp.asarray(c), jnp.asarray(nu), jnp.asarray(mu),
                 reg=0.02, tol=1e-7)
    assert float(r.marginal_err) < 1e-6
    p = np.asarray(r.plan)
    np.testing.assert_allclose(p.sum(0), mu, atol=1e-4)
    opt = exact_ot_cost(c, nu, mu)
    # entropic plan cost is close to opt for small reg; must exceed opt - tiny
    assert float(r.cost) >= opt - 1e-5


def test_log_and_kernel_variants_agree_at_moderate_reg():
    c, nu, mu = _instance(25, 2)
    a = sinkhorn(jnp.asarray(c), jnp.asarray(nu), jnp.asarray(mu),
                 reg=0.1, tol=1e-8)
    b = sinkhorn(jnp.asarray(c), jnp.asarray(nu), jnp.asarray(mu),
                 reg=0.1, tol=1e-8, use_log=False)
    assert abs(float(a.cost) - float(b.cost)) < 1e-4


def test_kernel_variant_underflows_at_small_reg():
    """The paper's Section 5 observation: exp(-c/reg) underflows -> the
    kernel-matrix iteration degrades or stalls while log-domain stays fine."""
    c, nu, mu = _instance(30, 3)
    reg = 0.002
    k = np.exp(-c / reg)
    assert (k.sum(1) == 0).any()  # rows fully underflow in fp64 even
    rlog = sinkhorn(jnp.asarray(c), jnp.asarray(nu), jnp.asarray(mu),
                    reg=reg, tol=1e-7, max_iters=4000)
    assert np.isfinite(float(rlog.cost))


def test_reg_heuristic_monotone():
    assert reg_for_additive_eps(0.1, 100) > reg_for_additive_eps(0.01, 100)


def test_sinkhorn_kernel_in_solver_loop():
    """Pallas sinkhorn_row_update drops into the log-domain loop."""
    from repro.kernels import ops
    from repro.core.costs import build_cost_matrix

    rng = np.random.default_rng(0)
    n = 96
    c = build_cost_matrix(jnp.asarray(rng.uniform(size=(n, 2))),
                          jnp.asarray(rng.uniform(size=(n, 2))), "euclidean")
    nu = jnp.full((n,), 1.0 / n)
    log_nu = jnp.log(nu)
    reg = 0.05
    f = jnp.zeros((n,))
    g = jnp.zeros((n,))
    for _ in range(80):
        f = ops.sinkhorn_row_update(c, g, log_nu, reg)
        g = ops.sinkhorn_row_update(c.T, f, log_nu, reg)
    plan = jnp.exp((f[:, None] + g[None, :] - c) / reg)
    assert float(jnp.abs(plan.sum(1) - nu).sum()) < 2e-2
