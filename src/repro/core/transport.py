"""Optimal transport via the push-relabel framework (paper Section 4).

The paper reduces OT to an unbalanced assignment instance: scale masses by
theta = 4n/eps, round supplies down / demands up to integers, and replace each
node by unit copies. Lemma 4.1 shows copies of one vertex carry at most TWO
distinct dual values (exactly eps apart), so copies are never materialized:

  per supply b : ``y_b``  - dual of b's free copies (== max over copies);
                 ``free_b`` units of free supply. Matched-copy duals are
                 implicit: a matched pair is tight, y(b-copy) = c - y(a-copy).
  per demand a : ``ya_hi`` - max dual value among a's copies (<= 0);
                 ``free_a`` units of unmatched demand (always at dual 0, which
                 forces ya_hi == 0 while free_a > 0).
  flows        : ``F_hi[b,a]`` / ``F_lo[b,a]`` - units matched to a-copies at
                 ``ya_hi[a]`` / ``ya_hi[a] - 1`` respectively.

Only the *hi* cluster of a is ever admissible from free supply (the lo cluster
sits at slack >= 1), so each phase is a capacity-respecting greedy maximal
matching from free supply onto hi-cluster capacity, followed by push
(displacement of old flow picked up by new partners) and relabel. When a
column's hi cluster is fully consumed by M', its value collapses one step down
- precisely the mechanism that preserves eps-feasibility after free supply
duals rise (paper invariant I2, case (ii)).

All arithmetic is int32 in units of eps; the solve is one jitted XLA program.

Like the assignment solver, the loop is also exposed as a resumable stepped
core (``init_ot_state`` / ``run_ot_phases`` / ``ot_converged``) plus a
``ot_prologue`` / ``ot_epilogue`` split of the float pipeline, so the
compacting batch driver (core/compaction.py) can run a solve as a sequence
of k-phase dispatches bit-identical to the one-shot ``solve_ot_int``.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .matching import proposal_keys, vary_like


class OTState(NamedTuple):
    y_b: jnp.ndarray      # (nb,) int32 dual of free supply copies
    ya_hi: jnp.ndarray    # (na,) int32 max dual among demand copies (<= 0)
    free_b: jnp.ndarray   # (nb,) int32 unmatched supply units
    free_a: jnp.ndarray   # (na,) int32 unmatched demand units
    f_hi: jnp.ndarray     # (nb, na) int32 flow matched at ya_hi
    f_lo: jnp.ndarray     # (nb, na) int32 flow matched at ya_hi - 1
    phases: jnp.ndarray
    rounds: jnp.ndarray


class OTResult(NamedTuple):
    plan: jnp.ndarray     # (nb, na) float32, exact marginals (nu rows, mu cols)
    cost: jnp.ndarray     # <plan, C> under original costs
    y_b: jnp.ndarray      # scaled approximate duals (supply side)
    y_a: jnp.ndarray      # scaled approximate duals (demand side)
    phases: jnp.ndarray
    rounds: jnp.ndarray
    state: OTState        # raw integer state (for invariant checks)
    theta: float
    s_int: jnp.ndarray    # integer supplies after rounding
    d_int: jnp.ndarray    # integer demands after rounding


def _grant_round(c_int, y_b, ya_hi, rem_b, cap_a, salt):
    """One propose/accept round. Every b with remaining free supply proposes
    all of it to one hash-random admissible column with remaining capacity;
    columns grant FIFO by row order.

    A proposal's prefix is the exclusive cumsum of ALL proposing rows minus
    that of its column's first proposer, not a prefix over the column's own
    proposers: it over-counts whatever other columns' rows sit between them,
    so a column may stop short of its capacity and take further partial
    grants in later rounds of the same phase. The fused kernel
    (``kernels/fused_phase``) and every recorded answer follow this rule."""
    nb, na = c_int.shape
    adm = (y_b[:, None] + ya_hi[None, :] == c_int + 1) & (cap_a[None, :] > 0)
    keys = proposal_keys(nb, na, salt)
    keys = jnp.where(adm, keys, jnp.uint32(0xFFFFFFFF))
    best = jnp.argmin(keys, axis=1).astype(jnp.int32)
    can = jnp.any(adm, axis=1) & (rem_b > 0)
    tgt = jnp.where(can, best, jnp.int32(-1))

    amt = jnp.where(can, rem_b, 0)
    cums = jnp.cumsum(amt)
    excl = cums - amt
    big = jnp.iinfo(jnp.int32).max
    tgt_safe = jnp.where(can, tgt, na)
    base = jnp.full((na,), big, jnp.int32).at[tgt_safe].min(
        jnp.where(can, excl, big), mode="drop"
    )
    prefix = excl - jnp.where(can, base[jnp.clip(tgt, 0, na - 1)], 0)
    grant = jnp.clip(cap_a[jnp.clip(tgt, 0, na - 1)] - prefix, 0, amt)
    grant = jnp.where(can, grant, 0)
    return tgt_safe, grant, jnp.any(can)


# Rows of a phase's round log, R = min(_LOG_ROUNDS, max_rounds): nearly
# every phase of DOTmark-like OT fits one block of 32 rounds, and the log
# is 32/na of a flow matrix.
_LOG_ROUNDS = 32


def _phase(c_int, s: OTState, max_rounds: int) -> OTState:
    nb, na = c_int.shape
    free_b0, free_a0 = s.free_b, s.free_a
    # hi-cluster capacity available to M': free units (only live at value 0 ==
    # ya_hi) plus already-matched hi copies (displaceable).
    m_hi = jnp.sum(s.f_hi, axis=0)
    cap0 = jnp.where(s.ya_hi == 0, s.free_a, 0) + m_hi
    # Guard: free_a > 0 implies ya_hi == 0, so the where() is redundant by the
    # invariant but keeps the state safe if it is ever perturbed.

    # The round loop carries (nb,) vectors and logs each round's grants in
    # an (n_log, nb) buffer, added into the flows once per block of at most
    # n_log rounds: no (nb, na) array rides in the round carry, which the
    # drivers' vmap would otherwise select (copy) whole every round.
    n_log = min(_LOG_ROUNDS, max_rounds)
    rows = jnp.broadcast_to(jnp.arange(nb, dtype=jnp.int32), (n_log, nb))

    def round_cond(c):
        rem_b, cap_a, rounds, done, k, log_tgt, log_grant = c
        return (~done) & (rounds < max_rounds) & (k < n_log)

    def round_body(c):
        rem_b, cap_a, rounds, _, k, log_tgt, log_grant = c
        salt = s.phases * jnp.int32(7919) + rounds
        tgt_safe, grant, any_prop = _grant_round(
            c_int, s.y_b, s.ya_hi, rem_b, cap_a, salt
        )
        cap_a = cap_a.at[tgt_safe].add(-grant, mode="drop")
        rem_b = rem_b - grant
        return (rem_b, cap_a, rounds + 1, ~any_prop, k + 1,
                log_tgt.at[k].set(tgt_safe), log_grant.at[k].set(grant))

    def block(c):
        rem_b, cap_a, rounds, done, flows = c
        rem_b, cap_a, rounds, done, _, log_tgt, log_grant = jax.lax.while_loop(
            round_cond,
            round_body,
            vary_like((rem_b, cap_a, rounds, done, jnp.int32(0),
                       jnp.full((n_log, nb), na, jnp.int32),
                       jnp.zeros((n_log, nb), jnp.int32)),
                      c_int, *s),
        )
        # Unused log rows hold tgt = na, dropped by the scatter.
        flows = flows.at[rows, log_tgt].add(log_grant, mode="drop")
        return rem_b, cap_a, rounds, done, flows

    # The first block runs unconditionally; further blocks only for a phase
    # longer than n_log rounds, so under vmap the block loop (and its select
    # over the flows) runs no body unless some lane needs one.
    rem_b, cap_a, rounds, _, f_granted = jax.lax.while_loop(
        lambda c: (~c[3]) & (c[2] < max_rounds),
        block,
        vary_like(block((free_b0, cap0, jnp.int32(0), jnp.bool_(False),
                         s.f_lo)), c_int, *s),
    )

    g_a = cap0 - cap_a                                   # units matched in M'
    use_free = jnp.minimum(g_a, jnp.where(s.ya_hi == 0, free_a0, 0))
    disp = g_a - use_free                                # displaced hi flow
    # Victims: strip `disp` units off each column of f_hi, bottom rows first.
    suffix_excl = jnp.cumsum(s.f_hi[::-1], axis=0)[::-1] - s.f_hi
    take = jnp.clip(disp[None, :] - suffix_excl, 0, s.f_hi)
    f_hi = s.f_hi - take
    freed_b = jnp.sum(take, axis=1)

    # Relabel (III(a)): every M'-matched a-copy drops by one -> granted units
    # land at ya_hi - 1. If the hi cluster is now empty, the column collapses.
    free_a = free_a0 - use_free
    # Copies remaining at the hi value: surviving free units (they live at 0,
    # i.e. at ya_hi iff ya_hi == 0; free units are never displaced so a column
    # with free_a > 0 can never collapse) plus surviving matched-hi flow.
    hi_left = jnp.where(s.ya_hi == 0, free_a, 0) + jnp.sum(f_hi, axis=0)
    collapse = (hi_left == 0) & (g_a > 0)
    ya_hi = jnp.where(collapse, s.ya_hi - 1, s.ya_hi)
    # f_granted is s.f_lo plus this phase's grants.
    f_hi_new = jnp.where(collapse[None, :], f_granted, f_hi)
    f_lo_new = jnp.where(collapse[None, :], 0, f_granted)

    # Relabel (III(b)): rows of B' with free supply left after M' rise by one.
    rem_after = rem_b
    y_b = s.y_b + ((free_b0 > 0) & (rem_after > 0)).astype(jnp.int32)
    free_b = rem_after + freed_b

    return OTState(
        y_b=y_b,
        ya_hi=ya_hi,
        free_b=free_b,
        free_a=free_a,
        f_hi=f_hi_new,
        f_lo=f_lo_new,
        phases=s.phases + 1,
        rounds=s.rounds + rounds,
    )


def init_ot_state(s_int: jnp.ndarray, d_int: jnp.ndarray) -> OTState:
    """Paper initialization: all mass free, y(b) = eps (1 unit), y(a) = 0.

    ``free_b``/``free_a`` are forced to FRESH buffers (``copy=True``): an
    eager int32 ``astype`` would alias the caller's ``s_int``/``d_int``,
    and the chunked ``run_ot_phases`` donates the state — an aliased init
    would delete the caller's rounded masses out from under the epilogue."""
    nb = s_int.shape[0]
    na = d_int.shape[0]
    return OTState(
        y_b=jnp.ones((nb,), jnp.int32),
        ya_hi=jnp.zeros((na,), jnp.int32),
        free_b=jnp.array(s_int, dtype=jnp.int32, copy=True),
        free_a=jnp.array(d_int, dtype=jnp.int32, copy=True),
        f_hi=jnp.zeros((nb, na), jnp.int32),
        f_lo=jnp.zeros((nb, na), jnp.int32),
        phases=jnp.int32(0),
        rounds=jnp.int32(0),
    )


def ot_termination_threshold(nu, theta, eps: float) -> int:
    """Host-side float64 termination threshold ``int(eps * sum(s_int))``.

    ``s_int = floor(f32(nu) * f32(theta))`` replicates the device rounding
    exactly (a single correctly-rounded f32 multiply on either side); the
    eps product is then taken in float64. Computing it on device as
    ``f32(eps) * f32(total)`` rounds the wrong way for some (eps, total)
    pairs — e.g. eps=0.3/3 (the guaranteed path), total=10: f32(0.1)*10 =
    1.0000000149 -> 1, but float64 gives 0.999... -> 0 — the same bug PR 1
    fixed for the assignment path's ``int(eps * m)``."""
    s_int = np.floor(np.asarray(nu, np.float32) * np.float32(theta))
    return int(float(eps) * int(s_int.sum(dtype=np.float64)))


@partial(jax.jit, static_argnames=("eps", "max_phases", "max_rounds"))
def solve_ot_int(
    c_int: jnp.ndarray,
    s_int: jnp.ndarray,
    d_int: jnp.ndarray,
    eps: float,
    max_phases: int,
    max_rounds: int,
    threshold=None,
) -> OTState:
    """Run phases until free supply <= threshold. ``threshold`` (traced ()
    int32) should be the host-computed ``ot_termination_threshold``; when
    None (nu/theta unavailable on host, e.g. under a caller's jit) it falls
    back to the on-device f32 product."""
    if threshold is None:
        total_s = jnp.sum(s_int)
        threshold = (jnp.float32(eps)
                     * total_s.astype(jnp.float32)).astype(jnp.int32)
    else:
        threshold = jnp.asarray(threshold, jnp.int32)

    def cond(s: OTState):
        return (jnp.sum(s.free_b) > threshold) & (s.phases < max_phases)

    return jax.lax.while_loop(cond, lambda s: _phase(c_int, s, max_rounds),
                              init_ot_state(s_int, d_int))


@partial(jax.jit, static_argnames=("k", "max_rounds"), donate_argnums=(1,))
def run_ot_phases(
    c_int: jnp.ndarray,
    state: OTState,
    threshold,
    phase_cap,
    k: int,
    max_rounds: int,
) -> OTState:
    """Advance the OT solve by at most ``k`` phases (fewer on termination).

    ``threshold``/``phase_cap`` are traced () int32 (per instance under
    vmap); ``k`` and ``max_rounds`` are static. Chaining calls reproduces
    the one-shot ``solve_ot_int`` state trajectory bit for bit for any k:
    the phase body is the identical ``_phase`` and the per-phase salt rides
    in ``state.phases``.

    ``state`` is DONATED (the dominant buffers are the two (nb, na) flow
    matrices): a chunked solve updates them in place instead of holding
    two copies. Callers must rebind and drop the old reference."""
    threshold = jnp.asarray(threshold, jnp.int32)
    phase_cap = jnp.asarray(phase_cap, jnp.int32)
    start = state.phases

    def cond(s: OTState):
        return ((jnp.sum(s.free_b) > threshold) & (s.phases < phase_cap)
                & (s.phases - start < jnp.int32(k)))

    return jax.lax.while_loop(cond, lambda s: _phase(c_int, s, max_rounds),
                              state)


def ot_converged(state: OTState, threshold, phase_cap) -> jnp.ndarray:
    """() bool: the solve loop would not take another phase."""
    return ~((jnp.sum(state.free_b) > jnp.asarray(threshold, jnp.int32))
             & (state.phases < jnp.asarray(phase_cap, jnp.int32)))


def northwest_corner(r: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """Closed-form NW-corner plan: P[i,j] = (min(R_i,C_j) - max(R_{i-1},C_{j-1}))+"""
    cr = jnp.cumsum(r)
    cc = jnp.cumsum(c)
    cr0 = cr - r
    cc0 = cc - c
    return jnp.maximum(
        jnp.minimum(cr[:, None], cc[None, :])
        - jnp.maximum(cr0[:, None], cc0[None, :]),
        0.0,
    )


def ot_phase_cap(eps: float) -> int:
    """Static safety bound on the phase count (paper Lemma 4.2 analogue)."""
    return int((1.0 + 2.0 * eps) / (eps * eps)) + 8


def ot_prologue(c: jnp.ndarray, nu: jnp.ndarray, mu: jnp.ndarray, theta, eps):
    """Rounding half of the pipeline: float costs/masses -> integer instance.
    ``theta`` and ``eps`` may be Python floats or traced f32 scalars (the
    batched/compacting drivers vmap with per-instance values). Returns
    ``(c_int, s_int, d_int, scale)``."""
    c = jnp.asarray(c, jnp.float32)
    nu = jnp.asarray(nu, jnp.float32)
    mu = jnp.asarray(mu, jnp.float32)
    theta = jnp.asarray(theta, jnp.float32)
    scale = jnp.maximum(jnp.max(c), 1e-30)
    c_int = jnp.floor(c / scale / eps).astype(jnp.int32)
    s_int = jnp.floor(nu * theta).astype(jnp.int32)          # round down
    d_int = jnp.ceil(mu * theta).astype(jnp.int32)           # round up
    return c_int, s_int, d_int, scale


def ot_pipeline(
    c: jnp.ndarray,
    nu: jnp.ndarray,
    mu: jnp.ndarray,
    theta,
    eps: float,
    threshold=None,
) -> OTResult:
    """Traceable solve pipeline: rounding -> integer solve -> completion ->
    marginal repair. ``theta`` may be a Python float or a traced f32 scalar
    (the batched solver vmaps this function with a per-instance theta);
    ``threshold`` the host-computed ``ot_termination_threshold`` (traced ()
    int32, falls back to the on-device f32 product when None)."""
    c = jnp.asarray(c, jnp.float32)
    nu = jnp.asarray(nu, jnp.float32)
    mu = jnp.asarray(mu, jnp.float32)
    nb, na = c.shape
    c_int, s_int, d_int, scale = ot_prologue(c, nu, mu, theta, eps)
    theta = jnp.asarray(theta, jnp.float32)
    state = solve_ot_int(
        c_int, s_int, d_int, eps, ot_phase_cap(eps),
        max_rounds=int(nb + na + 2), threshold=threshold,
    )
    return ot_epilogue(c, nu, mu, theta, eps, scale, s_int, d_int, state)


def ot_epilogue(c, nu, mu, theta, eps, scale, s_int, d_int,
                state: OTState) -> OTResult:
    """Completion + marginal-repair half of the pipeline, applied to a
    terminated integer state. The compacting driver runs this once, in
    bulk, over the full batch of retired states."""
    theta = jnp.asarray(theta, jnp.float32)
    flow = (state.f_hi + state.f_lo).astype(jnp.float32)
    # Integer completion: leftover free supply -> leftover demand capacity.
    comp = northwest_corner(
        state.free_b.astype(jnp.float32), state.free_a.astype(jnp.float32)
    )
    plan = (flow + comp) / theta
    # Repair marginals to the *original* (nu, mu): demand round-up can
    # overshoot a column by < 1/theta; rescale columns then NW-fill residuals.
    colsum = jnp.sum(plan, axis=0)
    col_scale = jnp.where(colsum > mu, mu / jnp.maximum(colsum, 1e-30), 1.0)
    plan = plan * col_scale[None, :]
    r = jnp.maximum(nu - jnp.sum(plan, axis=1), 0.0)
    cc = jnp.maximum(mu - jnp.sum(plan, axis=0), 0.0)
    # balance tiny float drift before the NW fill
    tot = jnp.minimum(jnp.sum(r), jnp.sum(cc))
    r = r * jnp.where(jnp.sum(r) > 0, tot / jnp.maximum(jnp.sum(r), 1e-30), 0.0)
    cc = cc * jnp.where(jnp.sum(cc) > 0, tot / jnp.maximum(jnp.sum(cc), 1e-30), 0.0)
    plan = plan + northwest_corner(r, cc)

    cost = jnp.sum(plan * c)
    return OTResult(
        plan=plan,
        cost=cost,
        y_b=state.y_b.astype(jnp.float32) * eps * scale,
        y_a=state.ya_hi.astype(jnp.float32) * eps * scale,
        phases=state.phases,
        rounds=state.rounds,
        state=state,
        theta=theta,
        s_int=s_int,
        d_int=d_int,
    )


def solve_ot(
    c: jnp.ndarray,
    nu: jnp.ndarray,
    mu: jnp.ndarray,
    eps: float,
    *,
    theta: float | None = None,
    guaranteed: bool = False,
) -> OTResult:
    """epsilon-additive approximate OT (rows = supplies nu, cols = demands mu).

    Cost error is measured against costs scaled to [0, 1] (paper convention):
    w(plan) <= w(opt) + O(eps) * max(c). ``guaranteed=True`` runs at eps/3.
    """
    if guaranteed:
        eps = eps / 3.0
    c = jnp.asarray(c, jnp.float32)
    nb, na = c.shape
    if theta is None:
        theta = 4.0 * max(nb, na) / eps
    threshold = None
    if not isinstance(nu, jax.core.Tracer) and \
            not isinstance(theta, jax.core.Tracer):
        # eager: exact float64 termination threshold (the on-device f32
        # fallback inside solve_ot_int rounds wrong for some (eps, total))
        threshold = ot_termination_threshold(np.asarray(nu), theta, eps)
    else:
        import warnings

        warnings.warn(
            "solve_ot traced under jit/vmap: the termination threshold "
            "falls back to the on-device f32 product, which rounds "
            "differently from the eager float64 path for rare "
            "(eps, total_mass) pairs. Prefer eager solve_ot, or "
            "solve_ot_batched / the compacting driver, which precompute "
            "exact host thresholds.",
            stacklevel=2,
        )
    res = ot_pipeline(c, nu, mu, theta, eps, threshold=threshold)
    if not isinstance(res.theta, jax.core.Tracer):
        # eager: keep the historical Python-float theta (and avoid forcing
        # a device sync when called under jit/vmap, where this is a tracer)
        res = res._replace(theta=float(res.theta))
    return res


# --------------------------------------------------------------------------
# Static-audit registration (repro.analysis): the OT stepped core donates
# its state (the PR-3 bug lived in its init chain, registered from
# core/problem.py), and the one-shot solve's threshold=None fallback is the
# PR-2 on-device f32 threshold — registered under the "threshold" tag so
# the dtype-drift rule keeps it visible as an explicit baseline entry.
# --------------------------------------------------------------------------

from ..analysis import registry as _audit  # noqa: E402


def _trace_ot_chunk():
    m = n = 8
    return _audit.trace_entry(
        name="core.transport.run_ot_phases",
        fn=lambda c_int, state, threshold, phase_cap:
            run_ot_phases(c_int, state, threshold, phase_cap, 4,
                          max_rounds=int(m + n + 2)),
        args={
            "c_int": jnp.zeros((m, n), jnp.int32),
            "state": init_ot_state(jnp.ones((m,), jnp.int32),
                                   jnp.ones((n,), jnp.int32)),
            "threshold": jnp.int32(0),
            "phase_cap": jnp.int32(8),
        },
        donated={"state"},
        must_trace={"threshold", "phase_cap"},
        tags={"stepped-core", "ot"},
        source=__name__,
    )


def _trace_solve_ot_int_fallback():
    m = n = 8
    return _audit.trace_entry(
        name="core.transport.solve_ot_int[threshold=None]",
        fn=lambda c_int, s_int, d_int:
            solve_ot_int(c_int, s_int, d_int, 0.25, 8, max_rounds=18,
                         threshold=None),
        args={
            "c_int": jnp.zeros((m, n), jnp.int32),
            "s_int": jnp.ones((m,), jnp.int32),
            "d_int": jnp.ones((n,), jnp.int32),
        },
        tags={"threshold", "ot"},
        source=__name__,
    )


_audit.register("core.transport.run_ot_phases", _trace_ot_chunk,
                source=__name__)
_audit.register("core.transport.solve_ot_int[threshold=None]",
                _trace_solve_ot_int_fallback, source=__name__)
