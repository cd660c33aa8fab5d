"""ProblemSpec: the stepped-core contract shared by assignment and OT.

The paper presents two push-relabel solvers — Algorithm 1 (assignment,
O(n^2/eps)) and Algorithm 2 (general OT, O(n^2/eps^2)) — that share one
skeleton: scale/round the instance to integers, run phases until the free
supply drops below a termination threshold, then complete/price the
result. Every batch driver in this repo (lockstep vmap, convergence
compaction, mesh-distributed dispatch) iterates that same skeleton; this
module captures it once as a protocol so each driver is written ONCE and
bound to a problem by a spec object, instead of maintaining parallel
``_assign_*`` / ``_ot_*`` function families per driver.

Protocol methods, mapped to the paper's algorithm steps:

  ``prepare``       host-side batch prep: per-lane valid sizes (the
                    prologue masks the padding with them), per-instance
                    eps/theta, the host-float64 termination thresholds
                    (``int(eps * m)`` for Algorithm 1; ``int(eps *
                    sum(s_int))`` for Algorithm 2) and phase-cap safety
                    bounds (Lemma 3.3 / Lemma 4.2 analogues), plus
                    power-of-two batch padding with born-converged empty
                    instances.
  ``prologue``      Algorithm 1/2 step 0 — scaling and rounding: float
                    costs (and masses, for OT) to the integer instance
                    the phases operate on. Returns ``(data, ctx)``:
                    ``data`` feeds the phase loop, ``ctx`` is kept intact
                    for the epilogue.
  ``init_state``    the paper's initialization: all supply free,
                    y(b) = eps (one unit), y(a) = 0, zero flow.
  ``run_phases``    at most k phases of the main loop (each phase: one
                    deterministic propose/push-relabel sweep over the
                    admissible graph). Resumable: chaining calls is
                    bit-identical to the one-shot solve for any k.
  ``converged``     the loop guard — free supply <= threshold, or the
                    phase cap (safety bound) hit.
  ``epilogue``      completion + pricing: arbitrarily match the <= eps*m
                    leftover free supply (Algorithm 1) / emit the
                    rounded transport plan (Algorithm 2), price against
                    the float costs, scale duals back.

``prologue`` through ``epilogue`` are pure per-instance jax functions
over pytrees; drivers vmap/jit/shard_map them (see ``core/compaction``
and ``core/distributed``), so one spec serves every dispatch strategy.
The remaining methods are host-side glue: ragged-instance handling for
the ``core/api.solve`` front door, the lockstep fixed-shape path, and
the per-instance row/col matrix-sharded path of ``core/sharded``.

Two singleton specs are exported: ``ASSIGNMENT`` and ``OT``. They are
stateless; identity-hashing makes them usable as jit-cache keys.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Protocol, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .pushrelabel import (
    _max_phases,
    assignment_converged,
    assignment_epilogue,
    assignment_prologue,
    init_assignment_state,
    run_assignment_phases,
)
from .transport import (
    OTResult,
    OTState,
    init_ot_state,
    ot_converged,
    ot_epilogue,
    ot_phase_cap,
    ot_prologue,
    run_ot_phases,
)


def pow2_at_least(x: int) -> int:
    """Smallest power of two >= max(x, 1)."""
    p = 1
    while p < x:
        p *= 2
    return p


def lane_map(fn):
    """``fn`` applied to each lane of its (batched) arguments in turn.

    The float epilogues run through this instead of ``jax.vmap``: XLA
    compiles a vmapped epilogue differently for different batch widths (on
    a TPU v5e a one-lane batch orders the plan's float reductions unlike a
    wider one), so a lane's plan would depend on how many lanes shared its
    dispatch — a quarantine bisection, for one, re-dispatches survivors in
    smaller batches. A sequential map compiles one per-lane body for every
    width."""
    return lambda *args: jax.lax.map(lambda a: fn(*a), args)


def eps_array(eps, b: int, guaranteed: bool) -> np.ndarray:
    """(b,) host-float64 per-instance eps (the /3 of the guaranteed bound
    applied); shared by every driver so the scaling can never diverge."""
    arr = np.broadcast_to(np.asarray(eps, np.float64), (b,)).copy()
    if guaranteed:
        arr = arr / 3.0
    if (arr <= 0).any():
        raise ValueError("eps must be positive")
    return arr


class PreparedBatch(NamedTuple):
    """Host-side output of ``ProblemSpec.prepare``: device operands plus
    the host copies of the per-lane thresholds/caps the drivers schedule
    with. ``ops`` arrays all have the (bp,) dispatched batch leading."""
    ops: Dict[str, Any]        # operands for the (vmapped) prologue
    threshold: np.ndarray      # (bp,) int32 host-float64-derived
    phase_cap: np.ndarray      # (bp,) int32 safety bound per lane
    eps_arr: np.ndarray        # (bp,) float64 per-lane eps
    bp: int                    # dispatched batch (power of two)


class ProblemSpec(Protocol):
    """Stepped-core contract; see the module docstring for the mapping to
    the paper's Algorithm 1/2. Implementations must be stateless."""
    name: str

    # -- host-side batch prep ------------------------------------------
    def canonicalize(self, inputs: Dict[str, Any]) -> Dict[str, Any]: ...
    def batch_shape(self, inputs: Dict[str, Any]) -> Tuple[int, int, int]: ...
    def prepare(self, inputs, eps, *, sizes=None, guaranteed: bool = False,
                min_batch: int = 1, **kw) -> PreparedBatch: ...

    # names of ``ops`` entries the epilogue consumes VERBATIM: the drivers
    # merge them into ``ctx`` outside the jit boundary instead of routing
    # them through the prologue as pass-through outputs (which would
    # materialize a second device copy of the big operands)
    ctx_ops: Tuple[str, ...]

    # -- per-instance jax functions (drivers vmap/jit/shard_map these) --
    def prologue(self, ops: Dict[str, Any]): ...
    def init_state(self, data: Dict[str, Any], ctx: Dict[str, Any]): ...
    def run_phases(self, data: Dict[str, Any], state, k: int): ...
    def converged(self, data: Dict[str, Any], state): ...
    def epilogue(self, ctx: Dict[str, Any], state): ...

    # -- result shaping ------------------------------------------------
    def empty_result(self, m: int, n: int): ...
    def trim(self, r, b: int): ...

    # -- ragged front door / lockstep / matrix placement ---------------
    def instance_shape(self, inst) -> Tuple[int, int]: ...
    def pad_group(self, insts, key) -> Dict[str, Any]: ...
    def solve_lockstep(self, inputs, eps: float, *, sizes=None,
                       guaranteed: bool = False,
                       keep_state: bool = False, **kw): ...
    def matrix_instance(self, host, i, mi, ni, mp, np_, eps_i, mesh2,
                        row_axis, col_axis, **kw): ...
    def matrix_stack(self, rows, m_valid, n_valid, m: int, n: int): ...

    # -- per-artifact producers (the Solution surface) ------------------
    # The host-side epilogue is split per artifact so un-requested
    # artifacts (above all the dense (B, M, N) plan and the raw integer
    # state) are never materialized on host: ``artifact_device`` hands the
    # DEVICE arrays for one artifact to core/solution.py, which fetches
    # them lazily and at most once.
    artifacts: Tuple[str, ...]
    # whether the spec's RESULT already carries the pre-completion state
    # (OT does; assignment needs the dispatch to retain it explicitly)
    state_on_result: bool

    def artifact_device(self, name: str, r, state) -> Dict[str, Any]: ...
    def artifact_plan_dense(self, host: Dict[str, np.ndarray], batch: int,
                            shape: Tuple[int, int]) -> np.ndarray: ...
    def artifact_plan_sparse(self, r, fetch, batch: int,
                             shape: Tuple[int, int]): ...
    def artifact_state(self, r, state): ...
    def legacy_instance_dict(self, sol) -> Dict[str, Any]: ...


def _sizes_arrays(sizes, b, m, n):
    """Host-side (B,) m_valid / n_valid arrays (full shape when sizes=None)."""
    if sizes is None:
        return (np.full((b,), m, np.int32), np.full((b,), n, np.int32))
    sizes = np.asarray(sizes, np.int32)
    if sizes.shape != (b, 2):
        raise ValueError(f"sizes must be ({b}, 2), got {sizes.shape}")
    if (sizes[:, 0] > m).any() or (sizes[:, 1] > n).any():
        raise ValueError("instance size exceeds padded bucket shape")
    return sizes[:, 0].copy(), sizes[:, 1].copy()


def _theta_array(sizes_m, sizes_n, eps, theta) -> np.ndarray:
    """Per-instance theta = 4*max(m, n)/eps, computed on host in float64 and
    cast to f32 so it is bit-identical to the unbatched solve_ot default.
    ``eps`` may be a scalar or a (B,) array (compacting driver)."""
    if theta is not None:
        return np.broadcast_to(
            np.asarray(theta, np.float32), sizes_m.shape
        ).copy()
    eps = np.asarray(eps, np.float64)
    return (4.0 * np.maximum(sizes_m, sizes_n) / eps).astype(np.float32)


def _ot_thresholds(nu, m_valid, theta, eps) -> np.ndarray:
    """(B,) int32 per-instance termination thresholds, computed on the
    host in float64 from the masses of each instance's valid rows (the
    rows at or past ``m_valid`` count as zero) — identical to the
    unbatched solve_ot (the on-device f32 product rounds the wrong way
    for some (eps, total_mass) pairs). Reads only the (B, M) masses.
    Shared by the lockstep and compacting paths so the two can never
    diverge on the threshold. ``eps`` scalar or (B,)."""
    nu = np.asarray(nu, np.float32)
    b, m = nu.shape
    row_ok = np.arange(m)[None, :] < m_valid[:, None]
    eps_b = np.broadcast_to(np.asarray(eps, np.float64), (b,))
    nu_h = np.where(row_ok, nu, np.float32(0.0))
    # vectorized ot_termination_threshold: f32 floor(nu * theta) per entry
    # (the device rounding), f64 row sums, f64 eps product, truncation
    s_rows = np.floor(nu_h * np.asarray(theta, np.float32)[:, None])
    return (eps_b * s_rows.sum(axis=1, dtype=np.float64)).astype(np.int64) \
        .astype(np.int32)


def mask_ot_padding(c, nu, mu, m_valid, n_valid):
    """Zero one OT instance's costs and masses outside its leading
    ``(m_valid, n_valid)`` block (traced, per lane). Every OT program
    that reads ``c``/``nu``/``mu`` (the prologue, the epilogue, the
    lockstep solve) masks through this first, so whatever the padding
    holds (stale data, inf, NaN) never reaches ``max(c)``, the integer
    instance or the plan's cost, and no masked copy of the batch is
    built outside those programs."""
    m, n = c.shape
    row_ok = jnp.arange(m) < m_valid
    col_ok = jnp.arange(n) < n_valid
    c = jnp.where(row_ok[:, None] & col_ok[None, :], c, 0.0)
    return c, jnp.where(row_ok, nu, 0.0), jnp.where(col_ok, mu, 0.0)


def _pad_lanes(bp: int, b: int, arrays: Dict[str, Any],
               fills: Dict[str, Any] | None = None) -> Dict[str, Any]:
    """Pad every (b, ...) array in ``arrays`` up to ``bp`` lanes with
    zeros (born-converged empty instances: zero valid rows / zero mass ->
    free supply 0 <= threshold 0). ``fills`` overrides the pad value per
    key — eps/theta lanes must stay nonzero so the prologue's divisions
    remain finite (the lanes are born converged regardless)."""
    if bp == b:
        return arrays
    out = {}
    for k, a in arrays.items():
        fill = (fills or {}).get(k, 0)
        if isinstance(a, np.ndarray):
            pad = np.full((bp - b,) + a.shape[1:], fill, a.dtype)
            out[k] = np.concatenate([a, pad])
        else:
            pad = jnp.full((bp - b,) + a.shape[1:], fill, a.dtype)
            out[k] = jnp.concatenate([a, pad])
    return out


# --------------------------------------------------------------------------
# Assignment (paper Algorithm 1)
# --------------------------------------------------------------------------

class AssignmentSpec:
    """ProblemSpec instance for the assignment solver (Algorithm 1),
    built from the stepped core in ``core/pushrelabel``."""

    name = "assignment"

    # -- host-side batch prep ------------------------------------------

    def canonicalize(self, inputs):
        c = jnp.asarray(inputs["c"], jnp.float32)
        if c.ndim != 3:
            raise ValueError(f"expected (B, M, N) costs, got shape {c.shape}")
        return {"c": c}

    def batch_shape(self, inputs):
        return inputs["c"].shape

    def prepare(self, inputs, eps, *, sizes=None, guaranteed: bool = False,
                min_batch: int = 1) -> PreparedBatch:
        """Masking/threshold/padding half of a batched assignment solve.

        Pads the batch to ``max(pow2_at_least(B), min_batch)`` with
        born-converged empty instances (the distributed driver passes
        ``min_batch = device count`` so the batch axis starts divisible
        by the mesh). Thresholds are host float64, identical to the
        unbatched ``int(eps * m)``."""
        c = inputs["c"]
        b, m, n = c.shape
        m_valid, n_valid = _sizes_arrays(sizes, b, m, n)
        eps_arr = eps_array(eps, b, guaranteed)
        threshold = np.asarray(
            [int(e * int(mi)) for e, mi in zip(eps_arr, m_valid)], np.int32
        )
        phase_cap = np.asarray([_max_phases(float(e), m) for e in eps_arr],
                               np.int32)
        bp = max(pow2_at_least(b), pow2_at_least(min_batch))
        ops = _pad_lanes(bp, b, {
            "c": c,
            "eps": eps_arr.astype(np.float32),
            "m_valid": m_valid,
            "n_valid": n_valid,
            "threshold": threshold,
            "phase_cap": phase_cap,
        }, fills={"eps": np.float32(eps_arr[0])})
        if bp > b:
            eps_arr = np.concatenate(
                [eps_arr, np.full((bp - b,), eps_arr[0])])
        return PreparedBatch(ops=ops, threshold=np.asarray(ops["threshold"]),
                             phase_cap=np.asarray(ops["phase_cap"]),
                             eps_arr=eps_arr, bp=bp)

    # -- per-instance jax functions ------------------------------------

    ctx_ops = ("eps",)

    def prologue(self, ops):
        cm, c_int, scale, row_ok, col_ok = assignment_prologue(
            ops["c"], ops["eps"], ops["m_valid"], ops["n_valid"])
        data = {"c_int": c_int, "threshold": ops["threshold"],
                "phase_cap": ops["phase_cap"], "m_valid": ops["m_valid"]}
        ctx = {"cm": cm, "scale": scale, "row_ok": row_ok, "col_ok": col_ok}
        return data, ctx

    def init_state(self, data, ctx):
        m, n = data["c_int"].shape
        return init_assignment_state(m, n)

    def run_phases(self, data, state, k: int):
        return run_assignment_phases(
            data["c_int"], state, data["threshold"], data["phase_cap"], k,
            m_valid=data["m_valid"])

    def converged(self, data, state):
        return assignment_converged(state, data["threshold"],
                                    data["phase_cap"],
                                    m_valid=data["m_valid"])

    def epilogue(self, ctx, state):
        return assignment_epilogue(ctx["cm"], ctx["scale"], state,
                                   ctx["eps"], ctx["row_ok"], ctx["col_ok"])

    # -- result shaping ------------------------------------------------

    def empty_result(self, m: int, n: int):
        from .batched import BatchedAssignmentResult

        z = lambda *s: jnp.zeros(s, jnp.float32)
        return BatchedAssignmentResult(
            matching=jnp.zeros((0, m), jnp.int32), cost=z(0),
            y_b=z(0, m), y_a=z(0, n),
            phases=jnp.zeros((0,), jnp.int32),
            rounds=jnp.zeros((0,), jnp.int32),
            matched_before_completion=jnp.zeros((0,), jnp.int32),
        )

    def trim(self, r, b: int):
        from .batched import BatchedAssignmentResult

        return BatchedAssignmentResult(
            matching=r.matching[:b],
            cost=r.cost[:b],
            y_b=r.y_b[:b],
            y_a=r.y_a[:b],
            phases=r.phases[:b],
            rounds=r.rounds[:b],
            matched_before_completion=r.matched_before_completion[:b],
        )

    # -- ragged front door / lockstep ----------------------------------

    def instance_shape(self, inst):
        return tuple(np.asarray(inst).shape)

    def pad_group(self, insts, key):
        from .batched import pad_stack

        return {"c": pad_stack(list(insts), key)}

    def solve_lockstep(self, inputs, eps: float, *, sizes=None,
                       guaranteed: bool = False, keep_state: bool = False):
        from .batched import solve_assignment_batched

        if keep_state:
            return solve_assignment_batched(
                inputs["c"], eps, sizes=sizes, guaranteed=guaranteed,
                keep_state=True)
        return solve_assignment_batched(inputs["c"], eps, sizes=sizes,
                                        guaranteed=guaranteed), None

    # -- per-artifact producers ----------------------------------------
    # Algorithm 1's deliverables, one producer each: the primal matching
    # (and its unit transport-plan view), the scaled approximate duals,
    # the objective, and the raw integer pre-completion state.

    artifacts = ("cost", "duals", "matching", "plan", "plan_sparse",
                 "state", "stats")
    state_on_result = False

    def artifact_device(self, name, r, state):
        if name == "cost":
            return {"cost": r.cost}
        if name == "scalars":
            return {"phases": r.phases, "rounds": r.rounds}
        if name == "duals":
            return {"y_b": r.y_b, "y_a": r.y_a}
        if name in ("matching", "plan"):
            # the dense plan is DERIVED from the compact matching on host;
            # only the (B, M) matching ever crosses device->host
            return {"matching": r.matching}
        raise KeyError(name)

    def artifact_plan_dense(self, host, batch, shape):
        m, n = shape
        matching = host["matching"][:batch]
        out = np.zeros((batch, m, n), np.float32)
        b_idx, r_idx = np.nonzero(matching >= 0)
        out[b_idx, r_idx, matching[b_idx, r_idx]] = 1.0
        return out

    def artifact_plan_sparse(self, r, fetch, batch, shape):
        from .solution import SparsePlanBatch

        m, n = shape
        matching = fetch("matching")["matching"][:batch].astype(np.int64)
        valid = matching >= 0
        nnz = valid.sum(axis=1).astype(np.int32)
        k = min(pow2_at_least(int(nnz.max(initial=1))), max(m * n, 1))
        idx = np.full((batch, k), m * n, np.int32)
        vals = np.zeros((batch, k), np.float32)
        for j in range(batch):
            rows = np.flatnonzero(valid[j])
            idx[j, :rows.size] = rows * n + matching[j, rows]
            vals[j, :rows.size] = 1.0
        return SparsePlanBatch(idx=idx, vals=vals, nnz=nnz,
                               shape=(int(m), int(n)))

    def artifact_state(self, r, state):
        # BatchedAssignmentResult carries no state: it exists only when
        # the dispatch retained it (keep_state / want=("state",))
        return state

    def legacy_instance_dict(self, sol):
        y_b, y_a = sol.duals()
        return {
            "matching": sol.matching(),
            "cost": sol.cost,
            "phases": sol.phases,
            "rounds": sol.rounds,
            "y_b": y_b,
            "y_a": y_a,
        }

    # -- matrix placement (row/col sharding per large instance) --------

    def matrix_instance(self, host, i, mi, ni, mp, np_, eps_i, mesh2,
                        row_axis, col_axis):
        from .sharded import solve_assignment_sharded

        # pad up to mesh-divisible dims (sharded dims must divide the
        # mesh); the PAD_COST/masked-completion machinery makes the
        # padded solve equal the unpadded one
        ci = np.zeros((mp, np_), np.float32)
        ci[:mi, :ni] = host["c"][i, :mi, :ni]
        return solve_assignment_sharded(
            ci, eps_i, mesh2, row_axis=row_axis, col_axis=col_axis,
            m_valid=mi, n_valid=ni,
        )

    def matrix_stack(self, rows, m_valid, n_valid, m: int, n: int):
        from .batched import BatchedAssignmentResult

        b = len(rows)
        matching = np.full((b, m), -1, np.int32)
        cost = np.zeros((b,), np.float32)
        y_b = np.zeros((b, m), np.float32)
        y_a = np.zeros((b, n), np.float32)
        phases = np.zeros((b,), np.int32)
        rounds = np.zeros((b,), np.int32)
        mbc = np.zeros((b,), np.int32)
        for i, r in enumerate(rows):
            mi, ni = int(m_valid[i]), int(n_valid[i])
            matching[i, :mi] = np.asarray(r.matching)[:mi]
            cost[i] = float(r.cost)
            y_b[i, :mi] = np.asarray(r.y_b)[:mi]
            y_a[i, :ni] = np.asarray(r.y_a)[:ni]
            phases[i] = int(r.phases)
            rounds[i] = int(r.rounds)
            mbc[i] = int(r.matched_before_completion)
        return BatchedAssignmentResult(
            matching=jnp.asarray(matching), cost=jnp.asarray(cost),
            y_b=jnp.asarray(y_b), y_a=jnp.asarray(y_a),
            phases=jnp.asarray(phases), rounds=jnp.asarray(rounds),
            matched_before_completion=jnp.asarray(mbc),
        )


# --------------------------------------------------------------------------
# General OT (paper Algorithm 2)
# --------------------------------------------------------------------------

class OTSpec:
    """ProblemSpec instance for the general OT solver (Algorithm 2),
    built from the stepped core in ``core/transport``."""

    name = "ot"

    # -- host-side batch prep ------------------------------------------

    def canonicalize(self, inputs):
        c = jnp.asarray(inputs["c"], jnp.float32)
        if c.ndim != 3:
            raise ValueError(f"expected (B, M, N) costs, got shape {c.shape}")
        return {"c": c,
                "nu": jnp.asarray(inputs["nu"], jnp.float32),
                "mu": jnp.asarray(inputs["mu"], jnp.float32)}

    def batch_shape(self, inputs):
        return inputs["c"].shape

    def prepare(self, inputs, eps, *, sizes=None, guaranteed: bool = False,
                min_batch: int = 1, theta=None) -> PreparedBatch:
        """OT counterpart of ``AssignmentSpec.prepare``. ``c``, ``nu``
        and ``mu`` pass through as the caller's buffers: the padding
        outside each lane's ``(m_valid, n_valid)`` block is masked inside
        the prologue and epilogue programs (``mask_ot_padding``), so no
        (B, M, N) array is built here, on the host or the device. The
        thresholds are host float64 from the masses (``_ot_thresholds``,
        shared with the lockstep path). Batch padding is born-converged
        (``m_valid = 0``: zero mass -> free supply 0 <= threshold 0)."""
        c, nu, mu = inputs["c"], inputs["nu"], inputs["mu"]
        b, m, n = c.shape
        m_valid, n_valid = _sizes_arrays(sizes, b, m, n)
        eps_arr = eps_array(eps, b, guaranteed)
        th = _theta_array(m_valid, n_valid, eps_arr, theta)
        phase_cap = np.asarray([ot_phase_cap(float(e)) for e in eps_arr],
                               np.int32)
        threshold = _ot_thresholds(nu, m_valid, th, eps_arr)
        bp = max(pow2_at_least(b), pow2_at_least(min_batch))
        ops = _pad_lanes(bp, b, {
            "c": c, "nu": nu, "mu": mu,
            "eps": eps_arr.astype(np.float32),
            "theta": th,
            "m_valid": m_valid,
            "n_valid": n_valid,
            "threshold": threshold,
            "phase_cap": phase_cap,
        }, fills={"eps": np.float32(eps_arr[0]), "theta": np.float32(1.0)})
        if bp > b:
            eps_arr = np.concatenate(
                [eps_arr, np.full((bp - b,), eps_arr[0])])
        return PreparedBatch(ops=ops, threshold=np.asarray(ops["threshold"]),
                             phase_cap=np.asarray(ops["phase_cap"]),
                             eps_arr=eps_arr, bp=bp)

    # -- per-instance jax functions ------------------------------------

    ctx_ops = ("c", "nu", "mu", "theta", "eps", "m_valid", "n_valid")

    def prologue(self, ops):
        c, nu, mu = mask_ot_padding(ops["c"], ops["nu"], ops["mu"],
                                    ops["m_valid"], ops["n_valid"])
        c_int, s_int, d_int, scale = ot_prologue(c, nu, mu, ops["theta"],
                                                 ops["eps"])
        data = {"c_int": c_int, "threshold": ops["threshold"],
                "phase_cap": ops["phase_cap"]}
        ctx = {"scale": scale, "s_int": s_int, "d_int": d_int}
        return data, ctx

    def init_state(self, data, ctx):
        return init_ot_state(ctx["s_int"], ctx["d_int"])

    def run_phases(self, data, state, k: int):
        m, n = data["c_int"].shape
        return run_ot_phases(data["c_int"], state, data["threshold"],
                             data["phase_cap"], k, int(m + n + 2))

    def converged(self, data, state):
        return ot_converged(state, data["threshold"], data["phase_cap"])

    def epilogue(self, ctx, state):
        c, nu, mu = mask_ot_padding(ctx["c"], ctx["nu"], ctx["mu"],
                                    ctx["m_valid"], ctx["n_valid"])
        return ot_epilogue(c, nu, mu, ctx["theta"], ctx["eps"],
                           ctx["scale"], ctx["s_int"], ctx["d_int"], state)

    # -- result shaping ------------------------------------------------

    def empty_result(self, m: int, n: int):
        zf = lambda *s: jnp.zeros(s, jnp.float32)
        zi = lambda *s: jnp.zeros(s, jnp.int32)
        return OTResult(
            plan=zf(0, m, n), cost=zf(0), y_b=zf(0, m), y_a=zf(0, n),
            phases=zi(0), rounds=zi(0),
            state=OTState(y_b=zi(0, m), ya_hi=zi(0, n), free_b=zi(0, m),
                          free_a=zi(0, n), f_hi=zi(0, m, n),
                          f_lo=zi(0, m, n), phases=zi(0), rounds=zi(0)),
            theta=zf(0), s_int=zi(0, m), d_int=zi(0, n),
        )

    def trim(self, r, b: int):
        return jax.tree_util.tree_map(lambda a: a[:b], r)

    # -- ragged front door / lockstep ----------------------------------

    def instance_shape(self, inst):
        return tuple(np.asarray(inst[0]).shape)

    def pad_group(self, insts, key):
        from .batched import pad_stack

        mb, nb = key
        return {"c": pad_stack([c for c, _, _ in insts], (mb, nb)),
                "nu": pad_stack([nu for _, nu, _ in insts], (mb,)),
                "mu": pad_stack([mu for _, _, mu in insts], (nb,))}

    def solve_lockstep(self, inputs, eps: float, *, sizes=None,
                       guaranteed: bool = False, keep_state: bool = False,
                       theta=None):
        from .batched import solve_ot_batched

        r = solve_ot_batched(inputs["c"], inputs["nu"], inputs["mu"],
                             eps, sizes=sizes, theta=theta,
                             guaranteed=guaranteed)
        # the OT result already carries its pre-completion state
        return (r, r.state) if keep_state else (r, None)

    # -- per-artifact producers ----------------------------------------
    # Algorithm 2's deliverables, one producer each: the primal plan
    # (dense on demand, compact COO by default), the scaled approximate
    # duals of the clustered copies, the objective, and the raw integer
    # state for the Lemma 4.1 certificates.

    artifacts = ("cost", "duals", "plan", "plan_sparse", "state", "stats")
    state_on_result = True

    def artifact_device(self, name, r, state):
        if name == "cost":
            return {"cost": r.cost}
        if name == "scalars":
            return {"phases": r.phases, "rounds": r.rounds,
                    "theta": r.theta}
        if name == "duals":
            return {"y_b": r.y_b, "y_a": r.y_a}
        if name == "plan":
            return {"plan": r.plan}
        raise KeyError(name)

    def artifact_plan_dense(self, host, batch, shape):
        return host["plan"][:batch]

    def artifact_plan_sparse(self, r, fetch, batch, shape):
        from .solution import sparse_from_dense_device

        # compacted ON DEVICE: only the COO triplets cross to host
        return sparse_from_dense_device(r.plan, batch)

    def artifact_state(self, r, state):
        return state if state is not None else r.state

    def legacy_instance_dict(self, sol):
        return {
            "plan": sol.plan(),
            "cost": sol.cost,
            "phases": sol.phases,
            "rounds": sol.rounds,
            "theta": sol.theta,
        }

    # -- matrix placement ----------------------------------------------

    def matrix_instance(self, host, i, mi, ni, mp, np_, eps_i, mesh2,
                        row_axis, col_axis, theta=None):
        from .sharded import solve_ot_sharded

        # pad to mesh-divisible dims with zero mass/cost (inert lanes:
        # zero supply never proposes, zero demand grants nothing); theta
        # comes from the TRUE size so the trajectory equals the unpadded
        # solve's (host float64 -> f32, as _theta_array)
        ci = np.zeros((mp, np_), np.float32)
        ci[:mi, :ni] = host["c"][i, :mi, :ni]
        nui = np.zeros((mp,), np.float32)
        nui[:mi] = host["nu"][i, :mi]
        mui = np.zeros((np_,), np.float32)
        mui[:ni] = host["mu"][i, :ni]
        if theta is None:
            th_i = float(np.float32(4.0 * max(mi, ni) / np.float64(eps_i)))
        else:
            b = host["c"].shape[0]
            th_i = float(np.broadcast_to(
                np.asarray(theta, np.float32), (b,))[i])
        return solve_ot_sharded(
            ci, nui, mui, eps_i, mesh2, row_axis=row_axis,
            col_axis=col_axis, theta=th_i,
        )

    def matrix_stack(self, rows, m_valid, n_valid, m: int, n: int):
        b = len(rows)
        plan = np.zeros((b, m, n), np.float32)
        cost = np.zeros((b,), np.float32)
        y_b = np.zeros((b, m), np.float32)
        y_a = np.zeros((b, n), np.float32)
        phases = np.zeros((b,), np.int32)
        rounds = np.zeros((b,), np.int32)
        thetas = np.zeros((b,), np.float32)
        s_int = np.zeros((b, m), np.int32)
        d_int = np.zeros((b, n), np.int32)
        st = {
            "y_b": np.zeros((b, m), np.int32),
            "ya_hi": np.zeros((b, n), np.int32),
            "free_b": np.zeros((b, m), np.int32),
            "free_a": np.zeros((b, n), np.int32),
            "f_hi": np.zeros((b, m, n), np.int32),
            "f_lo": np.zeros((b, m, n), np.int32),
            "phases": np.zeros((b,), np.int32),
            "rounds": np.zeros((b,), np.int32),
        }
        for i, r in enumerate(rows):
            mi, ni = int(m_valid[i]), int(n_valid[i])
            plan[i, :mi, :ni] = np.asarray(r.plan)[:mi, :ni]
            cost[i] = float(r.cost)
            y_b[i, :mi] = np.asarray(r.y_b)[:mi]
            y_a[i, :ni] = np.asarray(r.y_a)[:ni]
            phases[i] = int(r.phases)
            rounds[i] = int(r.rounds)
            thetas[i] = float(r.theta)
            s_int[i, :mi] = np.asarray(r.s_int)[:mi]
            d_int[i, :ni] = np.asarray(r.d_int)[:ni]
            st["y_b"][i, :mi] = np.asarray(r.state.y_b)[:mi]
            st["ya_hi"][i, :ni] = np.asarray(r.state.ya_hi)[:ni]
            st["free_b"][i, :mi] = np.asarray(r.state.free_b)[:mi]
            st["free_a"][i, :ni] = np.asarray(r.state.free_a)[:ni]
            st["f_hi"][i, :mi, :ni] = np.asarray(r.state.f_hi)[:mi, :ni]
            st["f_lo"][i, :mi, :ni] = np.asarray(r.state.f_lo)[:mi, :ni]
            st["phases"][i] = int(r.state.phases)
            st["rounds"][i] = int(r.state.rounds)
        state = OTState(**{k: jnp.asarray(v) for k, v in st.items()})
        return OTResult(
            plan=jnp.asarray(plan), cost=jnp.asarray(cost),
            y_b=jnp.asarray(y_b), y_a=jnp.asarray(y_a),
            phases=jnp.asarray(phases), rounds=jnp.asarray(rounds),
            state=state, theta=jnp.asarray(thetas),
            s_int=jnp.asarray(s_int), d_int=jnp.asarray(d_int),
        )


# --------------------------------------------------------------------------
# Fused-kernel spec variants
# --------------------------------------------------------------------------
#
# Same protocol, same prologue/epilogue/trim/artifact surface — only
# ``run_phases`` differs: it dispatches the single fused Pallas kernel
# (``kernels/fused_phase``) that keeps the full solver state in VMEM
# across all k phases instead of bouncing it through HBM between
# ``slack_propose`` and the XLA state updates. The fused kernels are
# bit-identical to the stepped cores (asserted in
# tests/test_fused_phase.py), so every driver-level invariant — chained
# resumability, lockstep == compact, padded-lane inertness — carries
# over unchanged. Because ``core/compaction.spec_fns`` caches programs
# per spec IDENTITY, the fused singletons get their own jit program
# family automatically; ``name`` stays "assignment"/"ot" so result
# shaping, bucketing, and the serving layers treat them as the same
# problem. ``stepped`` points back at the base singleton — the checkify
# sanitizer (analysis/checkified.py) re-routes through it because it
# cannot instrument the inside of a Pallas kernel.


class FusedAssignmentSpec(AssignmentSpec):
    """AssignmentSpec whose k-phase loop is the fused Pallas kernel."""

    fused = True

    def run_phases(self, data, state, k: int):
        from ..kernels import ops as _kops

        return _kops.fused_run_assignment_phases(
            data["c_int"], state, data["threshold"], data["phase_cap"], k,
            m_valid=data["m_valid"])

    def _lockstep_k(self, eps_arr, m: int) -> int:
        return max(_max_phases(float(e), m) for e in eps_arr) + 1

    def solve_lockstep(self, inputs, eps: float, *, sizes=None,
                       guaranteed: bool = False, keep_state: bool = False):
        return _fused_lockstep(self, inputs, eps, sizes=sizes,
                               guaranteed=guaranteed, keep_state=keep_state)


class FusedOTSpec(OTSpec):
    """OTSpec whose k-phase loop is the fused Pallas kernel."""

    fused = True

    def run_phases(self, data, state, k: int):
        from ..kernels import ops as _kops

        m, n = data["c_int"].shape
        return _kops.fused_run_ot_phases(
            data["c_int"], state, data["threshold"], data["phase_cap"], k,
            int(m + n + 2))

    def _lockstep_k(self, eps_arr, m: int) -> int:
        return max(ot_phase_cap(float(e)) for e in eps_arr) + 1

    def solve_lockstep(self, inputs, eps: float, *, sizes=None,
                       guaranteed: bool = False, keep_state: bool = False,
                       theta=None):
        return _fused_lockstep(self, inputs, eps, sizes=sizes,
                               guaranteed=guaranteed, keep_state=keep_state,
                               theta=theta)


def _fused_lockstep(spec, inputs, eps, *, sizes, guaranteed, keep_state,
                    **prep_kw):
    """Lockstep for the fused specs: one compacting dispatch with k set
    above every phase cap, so the whole batch runs to termination in a
    single kernel launch — genuine lockstep semantics (no compaction ever
    fires) through the fused ``run_phases``. The base specs' lockstep
    delegates to ``core/batched``, which is hard-wired to the stepped
    while-loop cores; routing through the spec-generic compacting driver
    keeps the fused path out of that module entirely."""
    from .compaction import solve_compacting

    b, m, _ = (int(s) for s in np.shape(inputs["c"]))
    k_all = spec._lockstep_k(eps_array(eps, b, guaranteed), m)
    r, stats = solve_compacting(
        spec, inputs, eps, sizes=sizes, k=k_all, guaranteed=guaranteed,
        keep_state=keep_state, **prep_kw)
    return r, (stats.final_state if keep_state else None)


ASSIGNMENT = AssignmentSpec()
OT = OTSpec()
FUSED_ASSIGNMENT = FusedAssignmentSpec()
FUSED_OT = FusedOTSpec()
FusedAssignmentSpec.stepped = ASSIGNMENT
FusedOTSpec.stepped = OT
AssignmentSpec.fused = False
OTSpec.fused = False


def fused_variant(spec):
    """Map a base spec to its fused-kernel variant (identity on the fused
    singletons themselves). Specs outside this module register theirs by
    setting a ``fused_spec`` attribute (e.g. the portfolio's SINKHORN ->
    SINKHORN_KERNEL) so core never has to import them. Raises for unknown
    specs rather than guessing."""
    if getattr(spec, "fused", False):
        return spec
    if spec is ASSIGNMENT:
        return FUSED_ASSIGNMENT
    if spec is OT:
        return FUSED_OT
    alt = getattr(spec, "fused_spec", None)
    if alt is not None:
        return alt
    raise ValueError(f"no fused variant registered for spec {spec!r}")


# --------------------------------------------------------------------------
# Static-audit registration (repro.analysis): the prologue -> init_state
# chains are where the PR-3 donated-buffer aliasing bug lived — the state
# handed to the donating chunk dispatch must not share buffers with
# anything the epilogue (or the driver) still reads. The "state-init-chain"
# tag makes the donation-safety rule run its jaxpr alias analysis here.
# --------------------------------------------------------------------------

from ..analysis import registry as _audit  # noqa: E402


def _trace_assignment_state_chain():
    m = n = 8

    def chain(c, eps, m_valid, n_valid):
        data, ctx = ASSIGNMENT.prologue({
            "c": c, "eps": eps, "m_valid": m_valid, "n_valid": n_valid,
            "threshold": jnp.int32(0), "phase_cap": jnp.int32(8)})
        state = ASSIGNMENT.init_state(data, ctx)
        return {"state": state,
                "retained": {"c_int": data["c_int"], "cm": ctx["cm"],
                             "scale": ctx["scale"]}}

    return _audit.trace_entry(
        name="core.problem.assignment_state_chain",
        fn=chain,
        args={
            "c": jnp.zeros((m, n), jnp.float32),
            "eps": jnp.float32(0.1),
            "m_valid": jnp.int32(m),
            "n_valid": jnp.int32(n),
        },
        retained={"c"},
        must_trace={"eps", "m_valid", "n_valid"},
        tags={"state-init-chain", "assignment"},
        source=__name__,
    )


def _trace_ot_state_chain():
    m = n = 8

    def chain(c, nu, mu, theta, eps, m_valid, n_valid):
        data, ctx = OT.prologue({
            "c": c, "nu": nu, "mu": mu, "theta": theta, "eps": eps,
            "m_valid": m_valid, "n_valid": n_valid,
            "threshold": jnp.int32(0), "phase_cap": jnp.int32(8)})
        state = OT.init_state(data, ctx)
        return {"state": state,
                "retained": {"c_int": data["c_int"],
                             "s_int": ctx["s_int"], "d_int": ctx["d_int"],
                             "scale": ctx["scale"]}}

    return _audit.trace_entry(
        name="core.problem.ot_state_chain",
        fn=chain,
        args={
            "c": jnp.zeros((m, n), jnp.float32),
            "nu": jnp.full((m,), 1.0 / m, jnp.float32),
            "mu": jnp.full((n,), 1.0 / n, jnp.float32),
            "theta": jnp.float32(4.0 * m / 0.1),
            "eps": jnp.float32(0.1),
            "m_valid": jnp.int32(m),
            "n_valid": jnp.int32(n),
        },
        retained={"c", "nu", "mu"},
        must_trace={"eps", "theta", "m_valid", "n_valid"},
        tags={"state-init-chain", "ot"},
        source=__name__,
    )


_audit.register("core.problem.assignment_state_chain",
                _trace_assignment_state_chain, source=__name__)
_audit.register("core.problem.ot_state_chain", _trace_ot_state_chain,
                source=__name__)
