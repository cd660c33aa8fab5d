"""Hybrid solver: coarse Sinkhorn duals warm-start the push-relabel core.

The portfolio's third solver exploits that the two base solvers price the
SAME dual: Sinkhorn's log-domain potentials (f, g) on the normalized
costs c_hat = c/max(c) are, after scaling, exactly the eps-units the
push-relabel integer duals live in. A cheap low-accuracy Sinkhorn run
(eps clamped loose, iteration-capped) therefore produces an initial
``y_b`` that starts the push-relabel solve much closer to termination
than the paper's cold y(b) = 1 — and because the finish IS the
push-relabel solver, the result keeps the paper's <= OPT + eps * m bound
(``guaranteed=True`` certifies exactly as a pure push-relabel solve).

Correctness does not rest on the Sinkhorn duals being any good:
``round_duals`` CLIPS the rounded warm duals into the invariant polytope

    1 <= y_b(b) <= min_{a live} c_int(b, a) + 1          (I1 + I2, y_a = 0)

so every invariant the paper's analysis needs (core/feasibility.py
checks them: I1, I2, the Lemma 3.2 dual bound) holds by construction no
matter what stage 1 returned — a garbage warm start only costs phases,
never correctness. tests/test_portfolio.py asserts this via
``check_ot_invariants`` on the warm state and via cost/feasibility
parity with the cold-start solver.

``WARM_OT`` is a four-line OTSpec subclass: same prologue, phases,
convergence, epilogue — only ``init_state`` seeds ``y_b`` from the extra
``y_b0`` operand. It rides every driver (lockstep / compact / mesh)
because the drivers forward ``**prep_kw`` and the spec pads the operand
like any other lane array.
"""
from __future__ import annotations

from dataclasses import replace as _dc_replace

import jax
import jax.numpy as jnp
import numpy as np

from ..core.compaction import DEFAULT_CHUNK, solve_compacting
from ..core.problem import (
    OTSpec,
    PreparedBatch,
    _pad_lanes,
    eps_array,
)
from ..core.transport import init_ot_state, ot_phase_cap
from .sinkhorn_spec import SINKHORN

# Columns with no demand never constrain the row dual; stand-in "+inf"
# for the int32 min-reduction over live columns.
_INT_BIG = np.int32(2 ** 30)
# Stage-1 accuracy/effort: the warm start needs direction, not
# convergence. eps is clamped to at least this ...
_COARSE_EPS = 0.25
# ... and the Sinkhorn sweep count is capped outright.
_WARM_ITERS = 64


def _round_duals_one(c, mu, f, g, eps):
    """One instance: scaled-integer feasible y_b from Sinkhorn (f, g).

    f, g live on the normalized costs (c/scale); integer duals live in
    units of eps on the same normalization, so f/eps is the natural
    rounding. The column potential is absorbed conservatively (g's max
    over live columns) and the result clipped to [1, min_live c_int + 1]
    — with y_a = 0 (the cold-start value) that clip alone implies I1,
    I2, and the Lemma 3.2 bound (c_hat <= 1 => c_int <= floor(1/eps)).
    All clamping happens in the integer domain: no int -> float -> int
    round-trip for the precision audit to flag."""
    scale = jnp.maximum(jnp.max(c), 1e-30)
    c_int = jnp.floor(c / scale / eps).astype(jnp.int32)  # == ot_prologue
    live = mu > 0
    any_live = jnp.any(live)
    gmax = jnp.max(jnp.where(live, g, -jnp.inf))
    y_raw = jnp.floor((f + gmax) / eps).astype(jnp.int32) + 1
    cap = jnp.min(jnp.where(live[None, :], c_int, _INT_BIG), axis=1) + 1
    y_b = jnp.clip(y_raw, jnp.int32(1), cap)
    # no live demand (empty padded lane): cold-start value
    return jnp.where(any_live, y_b, jnp.int32(1))


@jax.jit
def round_duals(c, mu, f, g, eps):
    """(B, m) int32 warm row duals from batched Sinkhorn potentials.
    ``eps`` is the (B,) INTERNAL accuracy of the finishing solve (i.e.
    already divided by 3 under ``guaranteed``) — the integer grid the
    push-relabel instance is rounded on."""
    return jax.vmap(_round_duals_one)(c, mu, f, g, eps)


class _WarmOTSpec(OTSpec):
    """OTSpec whose initial state takes ``y_b`` from a ``y_b0`` operand
    (cold-start 1s when absent, so the spec degrades to plain OT)."""

    name = "warm_ot"

    def prepare(self, inputs, eps, *, sizes=None, guaranteed: bool = False,
                min_batch: int = 1, theta=None, y_b0=None) -> PreparedBatch:
        p = super().prepare(inputs, eps, sizes=sizes, guaranteed=guaranteed,
                            min_batch=min_batch, theta=theta)
        b, m, _ = inputs["c"].shape
        if y_b0 is None:
            y_b0 = np.ones((b, m), np.int32)
        ops = dict(p.ops)
        # padded lanes warm-start at the cold value (they are born
        # converged; the fill just keeps the state invariant-clean)
        ops.update(_pad_lanes(p.bp, b,
                              {"y_b0": jnp.asarray(y_b0, jnp.int32)},
                              fills={"y_b0": np.int32(1)}))
        return PreparedBatch(ops=ops, threshold=p.threshold,
                             phase_cap=p.phase_cap, eps_arr=p.eps_arr,
                             bp=p.bp)

    ctx_ops = OTSpec.ctx_ops + ("y_b0",)

    def init_state(self, data, ctx):
        st = init_ot_state(ctx["s_int"], ctx["d_int"])
        # fresh buffer: the chunk dispatch donates the state, and
        # ctx["y_b0"] is retained for the epilogue's ctx pytree — an
        # aliased init would free it out from under that dispatch
        return st._replace(y_b=jnp.array(ctx["y_b0"], jnp.int32,
                                         copy=True))

    def solve_lockstep(self, inputs, eps: float, *, sizes=None,
                       guaranteed: bool = False, keep_state: bool = False,
                       theta=None, y_b0=None):
        # one compacting dispatch with k above the phase cap — lockstep
        # semantics without teaching core/batched a warm-start operand
        # (same trick as the fused and sinkhorn specs)
        b = int(np.shape(inputs["c"])[0])
        eps_arr = eps_array(eps, b, guaranteed)
        k_all = max(ot_phase_cap(float(e)) for e in eps_arr) + 1
        r, stats = solve_compacting(
            self, inputs, eps, sizes=sizes, k=k_all, guaranteed=guaranteed,
            keep_state=keep_state, theta=theta, y_b0=y_b0)
        return r, (stats.final_state if keep_state else None)


WARM_OT = _WarmOTSpec()


def dispatch_hybrid(
    inputs,
    eps,
    *,
    sizes=None,
    policy=None,
    keep_state: bool = False,
    deadline=None,
    obs=None,
    theta=None,
    warm_iters: int = _WARM_ITERS,
):
    """Solve one pre-batched OT bucket hybrid-style: a coarse
    iteration-capped Sinkhorn stage (always batch-compact — it is the
    cheap stage), dual rounding, then the push-relabel finish dispatched
    under ``policy``'s mode/mesh/chunk with the warm ``y_b0``. Returns
    ``(OTResult, stats)`` with the finish driver's stats; stage-1
    dispatches are folded into ``stats.dispatches``."""
    from ..core.api import DispatchPolicy, dispatch

    policy = policy or DispatchPolicy()
    inputs = WARM_OT.canonicalize(inputs)
    b = int(inputs["c"].shape[0])
    eps_user = np.broadcast_to(np.asarray(eps, np.float64), (b,)).copy()

    # stage 1: coarse Sinkhorn, capped sweeps, state retained
    eps_coarse = np.maximum(eps_user, _COARSE_EPS)
    _, st1 = solve_compacting(
        SINKHORN, inputs, eps_coarse, sizes=sizes,
        k=policy.chunk or DEFAULT_CHUNK, keep_state=True,
        deadline=deadline, obs=obs, max_iters=warm_iters)
    warm = st1.final_state

    # stage 2: round the potentials onto the finish solve's integer grid
    # (the INTERNAL eps: /3 under the guaranteed contract). Stage 1 ran
    # on the canonicalized inputs with their padding zeroed by the
    # Sinkhorn prepare's masks — f/g outside the valid block are inert
    # and the clip bounds them anyway.
    eps_int = jnp.asarray(eps_array(eps_user, b, policy.guaranteed),
                          jnp.float32)
    y_b0 = round_duals(inputs["c"], inputs["mu"], warm.f, warm.g, eps_int)

    # stage 3: push-relabel finish under the caller's dispatch policy
    finish = _dc_replace(policy, solver="pushrelabel", fused=False)
    r, stats = dispatch(WARM_OT, inputs, eps, sizes=sizes, policy=finish,
                        keep_state=keep_state, deadline=deadline, obs=obs,
                        theta=theta, y_b0=y_b0)
    if stats is not None:
        try:
            stats.dispatches += int(st1.dispatches)
        except (AttributeError, TypeError):
            pass
    return r, stats


# --------------------------------------------------------------------------
# repro.analysis registration: the warm-start state chain (donation
# safety: the seeded y_b must be a fresh buffer, not an alias of the
# retained y_b0 operand) and the dual rounding itself (eps must stay a
# traced operand; int-domain clamps keep the precision rules clean).
# --------------------------------------------------------------------------

from ..analysis import registry as _audit  # noqa: E402


def _trace_round_duals():
    b, m, n = 2, 4, 4
    return _audit.trace_entry(
        name="portfolio.hybrid.round_duals",
        fn=lambda c, mu, f, g, eps: {"y_b0": round_duals(c, mu, f, g,
                                                         eps)},
        args={
            "c": jnp.linspace(0.0, 1.0, b * m * n).reshape(b, m, n)
                 .astype(jnp.float32),
            "mu": jnp.full((b, n), 1.0 / n, jnp.float32),
            "f": jnp.zeros((b, m), jnp.float32),
            "g": jnp.zeros((b, n), jnp.float32),
            "eps": jnp.full((b,), 0.1, jnp.float32),
        },
        must_trace={"eps"},
        tags={"hybrid"},
        source=__name__,
    )


def _trace_warm_state_chain():
    m = n = 8

    def chain(c, nu, mu, theta, eps, m_valid, n_valid, y_b0):
        data, ctx = WARM_OT.prologue({
            "c": c, "nu": nu, "mu": mu, "theta": theta, "eps": eps,
            "m_valid": m_valid, "n_valid": n_valid,
            "threshold": jnp.int32(0), "phase_cap": jnp.int32(64)})
        ctx = {**ctx, "y_b0": y_b0}
        state = WARM_OT.init_state(data, ctx)
        return {"state": state,
                "retained": {"c_int": data["c_int"],
                             "s_int": ctx["s_int"],
                             "d_int": ctx["d_int"],
                             "y_b0": y_b0}}

    return _audit.trace_entry(
        name="portfolio.hybrid.warm_state_chain",
        fn=chain,
        args={
            "c": jnp.zeros((m, n), jnp.float32),
            "nu": jnp.full((m,), 1.0 / m, jnp.float32),
            "mu": jnp.full((n,), 1.0 / n, jnp.float32),
            "theta": jnp.float32(320.0),
            "eps": jnp.float32(0.1),
            "m_valid": jnp.int32(m),
            "n_valid": jnp.int32(n),
            "y_b0": jnp.ones((m,), jnp.int32),
        },
        retained={"c", "nu", "mu", "y_b0"},
        tags={"state-init-chain", "hybrid"},
        source=__name__,
    )


_audit.register("portfolio.hybrid.round_duals", _trace_round_duals,
                source=__name__)
_audit.register("portfolio.hybrid.warm_state_chain",
                _trace_warm_state_chain, source=__name__)
