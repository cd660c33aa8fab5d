"""Distributed push-relabel: the cost matrix is sharded (rows=supply on
'data', cols=demand on 'model') and the *same* integer phase loop from
pushrelabel.py runs under pjit - the SPMD partitioner turns the row-argmin
propose into per-shard argmins + cross-shard min-reductions and the
scatter-min accept into per-shard scatters + all-reduce(min), i.e. exactly
the parallel schedule described in DESIGN.md 2.

Because proposals/acceptance use deterministic hash keys with min-reductions,
the distributed solve is BIT-IDENTICAL to the single-device solve (tested on
a forced multi-device CPU in tests/test_sharded_ot.py)."""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .pushrelabel import (
    AssignmentResult, complete_matching, round_costs, solve_assignment_int,
)



def solve_assignment_sharded(
    c: jnp.ndarray,
    eps: float,
    mesh: Mesh,
    *,
    row_axis: str = "data",
    col_axis: str = "model",
    guaranteed: bool = False,
    m_valid: int | None = None,
    n_valid: int | None = None,
) -> AssignmentResult:
    """Assignment solve with the cost matrix sharded across `mesh`.

    The input matrix is placed sharded; all phase state (duals, matchings)
    stays 1-D sharded along its natural axis. Output matches the
    single-device `solve_assignment` bit for bit.

    ``m_valid``/``n_valid`` mark the input as padded: only the leading
    (m_valid, n_valid) block is the real instance (padded edges get the
    batched solver's PAD_COST / masked-completion treatment, so the result
    equals the unpadded solve). The distributed matrix placement
    (core/distributed.py) uses this to pad instances up to mesh-divisible
    shapes — this jax requires sharded dims divisible by the mesh."""
    from .pushrelabel import assignment_epilogue, assignment_prologue

    if guaranteed:
        eps = eps / 3.0
    c = jnp.asarray(c, jnp.float32)
    m = c.shape[0]
    if m_valid is None:
        mv = nv = None
        threshold = None
        cm, c_int, scale, row_ok, col_ok = assignment_prologue(c, eps)
    else:
        mv = jnp.int32(int(m_valid))
        nv = jnp.int32(int(n_valid))
        threshold = jnp.int32(int(eps * int(m_valid)))
        cm, c_int, scale, row_ok, col_ok = assignment_prologue(
            c, eps, mv, nv)
    c_sharded = jax.device_put(
        c_int, NamedSharding(mesh, P(row_axis, col_axis))
    )
    state = _assign_solve_fn(mesh, row_axis, col_axis, float(eps))(
        c_sharded, mv, threshold)
    return assignment_epilogue(cm, scale, state, eps, row_ok, col_ok)


@lru_cache(maxsize=None)
def _assign_solve_fn(mesh: Mesh, row_axis: str, col_axis: str, eps: float):
    """One jitted sharded phase-loop per (mesh, axes, eps) — repeat calls
    (the distributed matrix placement loops over instances) hit the jit
    cache instead of re-tracing per call."""
    def _solve(ci, mv_, th_):
        return solve_assignment_int(ci, eps, m_valid=mv_, threshold=th_)

    return jax.jit(
        _solve,
        in_shardings=(NamedSharding(mesh, P(row_axis, col_axis)),
                      None, None),
    )


def solve_ot_sharded(
    c: jnp.ndarray,
    nu: jnp.ndarray,
    mu: jnp.ndarray,
    eps: float,
    mesh: Mesh,
    *,
    row_axis: str = "data",
    col_axis: str = "model",
    theta: float | None = None,
    guaranteed: bool = False,
):
    """General-OT solve with the cost matrix (and both flow matrices of the
    solver state) sharded across ``mesh`` - the GSPMD-auto counterpart of
    ``solve_assignment_sharded`` for the transport solver.

    The integer phase loop (``solve_ot_int``) is jitted with the cost
    matrix placed ``P(row_axis, col_axis)`` and masses placed along their
    natural axes; the SPMD partitioner turns the row-local grant rounds
    into per-shard work plus min/sum cross-shard reductions. All phase
    arithmetic is int32 in units of eps, so the distributed integer state
    is BIT-IDENTICAL to the single-device ``solve_ot`` state; the float
    epilogue then runs on the gathered state with the same eager op
    sequence as ``solve_ot``, so the plan/cost match bit for bit too."""
    from .transport import (
        ot_epilogue, ot_phase_cap, ot_prologue, ot_termination_threshold,
        solve_ot_int,
    )

    if guaranteed:
        eps = eps / 3.0
    c = jnp.asarray(c, jnp.float32)
    nu = jnp.asarray(nu, jnp.float32)
    mu = jnp.asarray(mu, jnp.float32)
    nb, na = c.shape
    if theta is None:
        theta = 4.0 * max(nb, na) / eps
    threshold = ot_termination_threshold(np.asarray(nu), theta, eps)
    c_int, s_int, d_int, scale = ot_prologue(c, nu, mu, theta, eps)

    sh_mat = NamedSharding(mesh, P(row_axis, col_axis))
    sh_row = NamedSharding(mesh, P(row_axis))
    sh_col = NamedSharding(mesh, P(col_axis))
    solve = _ot_solve_fn(mesh, row_axis, col_axis, float(eps),
                         int(nb + na + 2))
    state = solve(
        jax.device_put(c_int, sh_mat),
        jax.device_put(s_int, sh_row),
        jax.device_put(d_int, sh_col),
        jnp.int32(threshold),
    )
    # epilogue on the gathered state, op-for-op the eager solve_ot path
    state = jax.device_get(state)
    state = jax.tree_util.tree_map(jnp.asarray, state)
    res = ot_epilogue(c, nu, mu, theta, eps, scale, s_int, d_int, state)
    return res._replace(theta=float(res.theta))


@lru_cache(maxsize=None)
def _ot_solve_fn(mesh: Mesh, row_axis: str, col_axis: str, eps: float,
                 max_rounds: int):
    """One jitted sharded OT phase-loop per (mesh, axes, eps, round cap),
    mirroring ``_assign_solve_fn``."""
    from .transport import ot_phase_cap, solve_ot_int

    def _solve(ci, si, di, th):
        return solve_ot_int(ci, si, di, eps, ot_phase_cap(eps),
                            max_rounds, threshold=th)

    return jax.jit(
        _solve,
        in_shardings=(NamedSharding(mesh, P(row_axis, col_axis)),
                      NamedSharding(mesh, P(row_axis)),
                      NamedSharding(mesh, P(col_axis)), None),
    )


def lower_sharded_solver(n: int, eps: float, mesh: Mesh,
                         row_axis="data", col_axis="model"):
    """AOT artifact for the dry-run/roofline path: lower + compile the phase
    loop for an (n, n) cost matrix on `mesh` without allocating it."""
    sds = jax.ShapeDtypeStruct(
        (n, n), jnp.int32,
        sharding=NamedSharding(mesh, P(row_axis, col_axis)),
    )
    fn = jax.jit(partial(solve_assignment_int, eps=eps))
    return fn.lower(sds)


# ===========================================================================
# Explicit shard_map implementation - the paper's parallel schedule with
# hand-placed collectives (vs. the GSPMD-auto version above). Per round:
#   propose : row-local hash-argmin over the LOCAL column block, then two
#             lexicographic pmin's across the column axis (min key, then min
#             global column among blocks achieving it);
#   accept  : per column-block scatter-min of proposing global row ids, then
#             pmin across the row axis; one all_gather of the (n_loc,)
#             winners over the column axis so every row learns its verdict.
# Per phase, push/relabel are purely local except one all_gather of the
# displaced-partner ids. Cross-device traffic per round is O(m + n) ints -
# the n^2 work stays entirely shard-local, which is the whole point of the
# paper's O(log n / eps^2) parallel claim.
# ===========================================================================

from .matching import proposal_keys  # noqa: E402,F401  (hash must match exactly)

_BIG32 = jnp.int32(2**31 - 1)
_UMAX = jnp.uint32(0xFFFFFFFF)


def _propose_local(c_blk, y_b, y_a_blk, avail_blk, salt, r0, c0, m, n):
    """Per-row best (key, global col) within this block."""
    m_loc, n_loc = c_blk.shape
    adm = (y_b[:, None] + y_a_blk[None, :] == c_blk + 1) & avail_blk[None, :]
    # hash inputs must be pure uint32 (an int32 offset would promote and
    # change the keys vs the single-device proposal_keys)
    rows_g = (r0.astype(jnp.uint32)
              + jnp.arange(m_loc, dtype=jnp.uint32))[:, None]
    cols_g = (c0.astype(jnp.uint32)
              + jnp.arange(n_loc, dtype=jnp.uint32))[None, :]
    from .matching import _mix, _H1, _H2, _H3
    keys = _mix(rows_g * _H1 + cols_g * _H2
                + salt.astype(jnp.uint32) * _H3)
    keys = jnp.where(adm, keys, _UMAX)
    best_key = jnp.min(keys, axis=1)
    best_col = (c0 + jnp.argmin(keys, axis=1)).astype(jnp.int32)
    return best_key, jnp.where(best_key == _UMAX, _BIG32, best_col)


def _phase_shardmap(c_blk, carry, salt0, row_axis, col_axis, m, n,
                    m_loc, n_loc, max_rounds):
    y_b, y_a, match_ba, match_ab = carry
    r0 = jax.lax.axis_index(row_axis) * m_loc
    c0 = jax.lax.axis_index(col_axis) * n_loc
    rows_g = r0 + jnp.arange(m_loc, dtype=jnp.int32)
    cols_g = c0 + jnp.arange(n_loc, dtype=jnp.int32)
    in_bprime = match_ba < 0

    zero = jnp.sum(c_blk[:1, :1]) * 0

    def round_body(state):
        mprime_b, mprime_a, avail_blk, active_b, rounds, done = state
        salt = salt0 * jnp.int32(7919) + rounds
        bk, bc = _propose_local(c_blk, y_b, y_a, avail_blk, salt,
                                r0, c0, m, n)
        # pmin lowers unsigned to signed; use the order-preserving
        # uint32 -> int32 bijection (flip the sign bit) for the reduction.
        bks = jax.lax.bitcast_convert_type(
            bk ^ jnp.uint32(0x80000000), jnp.int32)
        # lexicographic min across column blocks: first the key...
        kmin = jax.lax.pmin(bks, col_axis)
        # ...then the smallest global column among blocks achieving kmin
        cand = jnp.where((bks == kmin) & (kmin != _BIG32), bc, _BIG32)
        prop = jax.lax.pmin(cand, col_axis)          # (m_loc,) global col
        prop = jnp.where(active_b & (prop != _BIG32), prop, -1)

        # accept: my column block scatters min proposing global row id
        local = (prop >= c0) & (prop < c0 + n_loc)
        tgt = jnp.where(local, prop - c0, n_loc)
        winners = jnp.full((n_loc,), _BIG32).at[tgt].min(
            jnp.where(local, rows_g, _BIG32), mode="drop")
        winners = jax.lax.pmin(winners, row_axis)     # (n_loc,) global rows
        # every row needs the winner of an arbitrary global column
        winners_all = jax.lax.all_gather(
            winners, col_axis, tiled=True)            # (n,)
        won = (prop >= 0) & (
            winners_all[jnp.clip(prop, 0, n - 1)] == rows_g)

        mprime_b = jnp.where(won, prop, mprime_b)
        won_col = (winners != _BIG32)
        mprime_a = jnp.where(won_col, winners, mprime_a)
        avail_blk = avail_blk & ~won_col
        active_b = active_b & ~won
        any_prop = jax.lax.pmax(
            jnp.any(prop >= 0).astype(jnp.int32), (row_axis, col_axis))
        done = jax.lax.pcast(any_prop == 0, (row_axis, col_axis),
                             to="varying")
        return (mprime_b, mprime_a, avail_blk, active_b, rounds + 1, done)

    init = (jnp.full((m_loc,), -1) + zero, jnp.full((n_loc,), _BIG32) + zero,
            (zero == 0) & jnp.ones((n_loc,), bool),
            in_bprime, zero, zero != 0)
    mprime_b, mprime_a, avail_blk, active_b, rounds, _ = jax.lax.while_loop(
        lambda s: (~s[5]) & (s[4] < max_rounds), round_body, init)

    # (II) push - my columns know their new and old partners
    won_col = mprime_a != _BIG32
    displaced = jnp.where(won_col & (match_ab >= 0), match_ab, -1)
    displaced_all = jax.lax.all_gather(displaced, col_axis, tiled=True)
    freed_mask_global = jnp.zeros((m,), bool).at[
        jnp.where(displaced_all >= 0, displaced_all, m)
    ].set(True, mode="drop")
    freed_mine = jax.lax.dynamic_slice_in_dim(freed_mask_global, r0, m_loc)
    match_ba = jnp.where(freed_mine, -1, match_ba)
    match_ba = jnp.where(mprime_b >= 0, mprime_b, match_ba)
    match_ab = jnp.where(won_col, mprime_a, match_ab)

    # (III) relabel - all local
    y_a = y_a - won_col.astype(jnp.int32)
    still_free = in_bprime & active_b
    y_b = y_b + still_free.astype(jnp.int32)
    return (y_b, y_a, match_ba, match_ab), rounds


def solve_assignment_shardmap(
    c: jnp.ndarray,
    eps: float,
    mesh: Mesh,
    *,
    row_axis: str = "data",
    col_axis: str = "model",
) -> AssignmentResult:
    """Manual-collective distributed push-relabel; bit-identical to
    solve_assignment (same hashes, same lexicographic tie-breaks)."""
    c = jnp.asarray(c, jnp.float32)
    m, n = c.shape
    n_row = mesh.shape[row_axis]
    n_col = mesh.shape[col_axis]
    assert m % n_row == 0 and n % n_col == 0, (m, n, dict(mesh.shape))
    m_loc, n_loc = m // n_row, n // n_col
    scale = jnp.maximum(jnp.max(c), 1e-30)
    c_int = round_costs(c / scale, eps)
    threshold = jnp.int32(int(eps * m))
    from .pushrelabel import _max_phases
    max_phases = _max_phases(eps, m)
    max_rounds = min(m, n) + 1

    def body(c_blk):
        zero = jnp.sum(c_blk[:1, :1]) * 0
        init = (
            jnp.ones((m_loc,), jnp.int32) + zero,       # y_b
            jnp.zeros((n_loc,), jnp.int32) + zero,      # y_a
            jnp.full((m_loc,), -1, jnp.int32) + zero,   # match_ba
            jnp.full((n_loc,), -1, jnp.int32) + zero,   # match_ab
            zero,                                        # phases
            zero,                                        # rounds
        )

        def cond(s):
            free = jax.lax.psum(
                jnp.sum(s[2] < 0, dtype=jnp.int32), (row_axis,))
            return (free > threshold) & (s[4] < jnp.int32(max_phases))

        def phase(s):
            carry, rounds = _phase_shardmap(
                c_blk, s[:4], s[4], row_axis, col_axis, m, n,
                m_loc, n_loc, max_rounds)
            return carry + (s[4] + 1, s[5] + rounds)

        y_b, y_a, mba, mab, ph, rd = jax.lax.while_loop(cond, phase, init)
        # declare replication along the orthogonal axis (values are equal
        # across it by construction; pmax makes that visible to the vma
        # checker so the out_specs below are accepted)
        return (
            jax.lax.pmax(y_b, col_axis),
            jax.lax.pmax(y_a, row_axis),
            jax.lax.pmax(mba, col_axis),
            jax.lax.pmax(mab, row_axis),
            jax.lax.pmax(ph, (row_axis, col_axis)),
            jax.lax.pmax(rd, (row_axis, col_axis)),
        )

    out = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=P(row_axis, col_axis),
        out_specs=(P(row_axis), P(col_axis), P(row_axis), P(col_axis),
                   P(), P()),
    ))(jax.device_put(c_int, NamedSharding(mesh, P(row_axis, col_axis))))
    y_b, y_a, match_ba, match_ab, phases, rounds = out

    matching = complete_matching(match_ba, match_ab)
    rows = jnp.arange(m)
    valid = matching >= 0
    cost = jnp.sum(
        jnp.where(valid, c[rows, jnp.clip(matching, 0, n - 1)], 0.0))
    return AssignmentResult(
        matching=matching,
        cost=cost,
        y_b=y_b.astype(jnp.float32) * eps * scale,
        y_a=y_a.astype(jnp.float32) * eps * scale,
        phases=phases,
        rounds=rounds,
        sum_ni=jnp.int32(-1),  # not tracked in the manual path
        matched_before_completion=jnp.sum(match_ba >= 0, dtype=jnp.int32),
    )
