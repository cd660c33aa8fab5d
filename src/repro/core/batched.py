"""Batched fixed-shape push-relabel solvers: B instances as one XLA program.

The paper's headline bound is *parallel* time O(log n / eps^2); serving many
small/medium OT instances means the win comes from amortizing one dispatch
across a batch (cf. the matrix-batched formulations of Altschuler-Weed-
Rigollet).  This module vmaps the existing single-instance ``lax.while_loop``
solvers over a leading batch axis (the float epilogue is the compacting
driver's own program).  JAX's while-loop batching rule runs the
lockstep loop until every instance's own predicate is false and select-masks
the carries of finished instances, so each instance executes *exactly* the
phase sequence it would have executed alone - results are bit-identical to
unbatched solves (up to the static round cap, which is derived from the
padded bucket shape and never binds in practice).

Ragged batches are handled by a padding/bucketing layer:

  * instances are padded up to a shape bucket (next power-of-two-ish size;
    shapes beyond the bucket table mint a ceil-pow2 bucket on the fly);
  * padded supply rows get zero mass / are masked out of the free set B';
  * padded demand columns get zero capacity (OT) or a cost so large that no
    dual sum can ever make them admissible (assignment);

so a padded instance walks the same admissible subgraph, with the same
deterministic hash keys (keys depend only on *global* (row, col, salt), not
on the matrix shape), as its unpadded original.

The ragged front ends are thin wrappers over the unified dispatch front
door (``core/api.solve``): ``compact``/``mesh`` arguments map onto a
:class:`~repro.core.api.DispatchPolicy`, and the lockstep fixed-shape
entry points below remain the single-dispatch building blocks the
``ASSIGNMENT``/``OT`` specs bind to.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .problem import (
    ASSIGNMENT,
    OT,
    _ot_thresholds,
    _sizes_arrays,
    _theta_array,
    mask_ot_padding,
    pow2_at_least,
)
from .pushrelabel import assignment_prologue, solve_assignment_int
from .transport import OTResult, ot_phase_cap, ot_prologue, solve_ot_int

DEFAULT_BUCKETS: tuple[int, ...] = (16, 32, 64, 128, 256, 512, 1024, 2048)


def next_bucket(k: int, buckets: Sequence[int] = DEFAULT_BUCKETS) -> int:
    """Smallest bucket >= k. Shapes beyond the biggest table entry mint a
    ceil-power-of-two bucket instead of a per-shape exact bucket, so a
    long tail of huge instances still shares compiled programs."""
    for b in buckets:
        if b >= k:
            return b
    return pow2_at_least(int(k))


# --------------------------------------------------------------------------
# Assignment
# --------------------------------------------------------------------------

class BatchedAssignmentResult(NamedTuple):
    matching: jnp.ndarray   # (B, M) int32, -1 beyond each instance's rows
    cost: jnp.ndarray       # (B,) float32
    y_b: jnp.ndarray        # (B, M) float32 scaled duals
    y_a: jnp.ndarray        # (B, N) float32 scaled duals
    phases: jnp.ndarray     # (B,) int32
    rounds: jnp.ndarray     # (B,) int32
    matched_before_completion: jnp.ndarray  # (B,) int32


def _epilogue(spec):
    """The compacting driver's epilogue program, so lockstep results equal
    compact (and mesh) results bit for bit on every backend."""
    from .compaction import DEFAULT_CHUNK, spec_fns

    return spec_fns(spec, DEFAULT_CHUNK)[4]


@partial(jax.jit, static_argnames=("eps",))
def _assignment_lockstep(c, m_valid, n_valid, threshold, eps: float):
    """Prologue + integer solve of every lane in ONE vmapped program;
    returns the ``ASSIGNMENT`` epilogue context and the pre-completion
    integer state."""

    def one(ci, mv, nv, th):
        cm, c_int, scale, row_ok, col_ok = assignment_prologue(
            ci, eps, mv, nv)
        st = solve_assignment_int(c_int, eps, m_valid=mv, threshold=th)
        ctx = {"cm": cm, "scale": scale, "row_ok": row_ok,
               "col_ok": col_ok, "eps": jnp.float32(eps)}
        return ctx, st

    return jax.vmap(one)(c, m_valid, n_valid, threshold)


def solve_assignment_batched(
    c: jnp.ndarray,
    eps: float,
    *,
    sizes=None,
    guaranteed: bool = False,
    keep_state: bool = False,
):
    """Solve B assignment instances stacked as one (B, M, N) cost tensor.

    Args:
      c: (B, M, N) nonnegative float costs; instance i occupies the leading
        ``sizes[i] = (m_i, n_i)`` block (m_i <= n_i), the rest is padding.
      eps: additive error parameter (shared across the batch - bucket
        dispatches share one compiled program per (shape, eps)).
      sizes: optional host (B, 2) int array of true instance shapes.
      keep_state: ALSO return the batched pre-completion integer state
        (``(BatchedAssignmentResult, PushRelabelState)`` instead of just
        the result) for feasibility certificates / the ``state``
        artifact of the Solution surface.
    """
    if guaranteed:
        eps = eps / 3.0
    c = jnp.asarray(c, jnp.float32)
    if c.ndim != 3:
        raise ValueError(f"expected (B, M, N) costs, got shape {c.shape}")
    b, m, n = c.shape
    m_valid, n_valid = _sizes_arrays(sizes, b, m, n)
    # Termination thresholds in host float64, matching the unbatched
    # int(eps * m) exactly (f32 rounding flips the floor for some eps).
    threshold = np.asarray([int(eps * int(mi)) for mi in m_valid], np.int32)
    args = (c, jnp.asarray(m_valid), jnp.asarray(n_valid),
            jnp.asarray(threshold))
    ctx, state = _assignment_lockstep(*args, eps)
    r = _epilogue(ASSIGNMENT)(ctx, state)
    out = BatchedAssignmentResult(
        matching=r.matching,
        cost=r.cost,
        y_b=r.y_b,
        y_a=r.y_a,
        phases=r.phases,
        rounds=r.rounds,
        matched_before_completion=r.matched_before_completion,
    )
    return (out, state) if keep_state else out


# --------------------------------------------------------------------------
# General OT
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("eps",))
def _ot_lockstep(c, nu, mu, m_valid, n_valid, theta, threshold, eps: float):
    """Prologue + integer solve of every lane in ONE vmapped program;
    returns the float part of the ``OT`` epilogue context and the
    integer state."""

    def one(ci, nui, mui, mv, nv, ti, thi):
        ci, nui, mui = mask_ot_padding(ci, nui, mui, mv, nv)
        c_int, s_int, d_int, scale = ot_prologue(ci, nui, mui, ti, eps)
        nb, na = ci.shape
        st = solve_ot_int(c_int, s_int, d_int, eps, ot_phase_cap(eps),
                          max_rounds=int(nb + na + 2), threshold=thi)
        ctx = {"theta": ti, "eps": jnp.float32(eps), "scale": scale,
               "s_int": s_int, "d_int": d_int}
        return ctx, st

    return jax.vmap(one)(c, nu, mu, m_valid, n_valid, theta, threshold)


def solve_ot_batched(
    c: jnp.ndarray,
    nu: jnp.ndarray,
    mu: jnp.ndarray,
    eps: float,
    *,
    sizes=None,
    theta=None,
    guaranteed: bool = False,
) -> OTResult:
    """Solve B general OT instances stacked as one (B, M, N) program.

    Args:
      c: (B, M, N) costs; nu: (B, M) supplies; mu: (B, N) demands. Instance i
        occupies the leading ``sizes[i]`` block; whatever the padding holds
        is masked to zero cost and mass inside the solve programs.
      eps: additive error parameter shared across the batch.
      sizes: optional host (B, 2) int array of true instance shapes - also
        sets the per-instance theta to the unbatched default 4*max(m,n)/eps.
      theta: optional scalar or (B,) override of the mass scaling.

    Returns an OTResult whose every leaf carries a leading batch axis.
    """
    if guaranteed:
        eps = eps / 3.0
    c = jnp.asarray(c, jnp.float32)
    nu = jnp.asarray(nu, jnp.float32)
    mu = jnp.asarray(mu, jnp.float32)
    if c.ndim != 3:
        raise ValueError(f"expected (B, M, N) costs, got shape {c.shape}")
    b, m, n = c.shape
    m_valid, n_valid = _sizes_arrays(sizes, b, m, n)
    th = _theta_array(m_valid, n_valid, eps, theta)
    thr = _ot_thresholds(nu, m_valid, th, eps)
    m_valid, n_valid = jnp.asarray(m_valid), jnp.asarray(n_valid)
    ctx, state = _ot_lockstep(c, nu, mu, m_valid, n_valid, jnp.asarray(th),
                              jnp.asarray(thr), eps)
    # the epilogue masks the caller's operands itself (as in the
    # compacting driver, they are not round-tripped through the program)
    ctx = {**ctx, "c": c, "nu": nu, "mu": mu, "m_valid": m_valid,
           "n_valid": n_valid}
    return _epilogue(OT)(ctx, state)


# --------------------------------------------------------------------------
# Ragged front end: bucket, pad, dispatch, unpad
# --------------------------------------------------------------------------

class _Bucketed(NamedTuple):
    key: tuple            # bucket shape key
    indices: list         # original instance positions
    sizes: np.ndarray     # (Bg, 2)


def bucket_instances(shapes, buckets: Sequence[int] = DEFAULT_BUCKETS):
    """Group instance shapes [(m_i, n_i)] into shape buckets.

    Returns a list of _Bucketed groups; every instance appears in exactly
    one group and ``key = (M, N)`` is the padded dispatch shape. Shapes
    larger than the biggest bucket get ceil-pow2 minted buckets (see
    ``next_bucket``)."""
    groups: dict = {}
    for i, (mi, ni) in enumerate(shapes):
        key = (next_bucket(int(mi), buckets), next_bucket(int(ni), buckets))
        groups.setdefault(key, []).append(i)
    out = []
    for key, idx in sorted(groups.items()):
        sizes = np.asarray([shapes[i] for i in idx], np.int32)
        out.append(_Bucketed(key=key, indices=idx, sizes=sizes))
    return out


def pad_stack(arrays, shape) -> jnp.ndarray:
    """Zero-pad each array up to ``shape`` and stack on a new batch axis."""
    out = []
    for a in arrays:
        a = np.asarray(a, np.float32)
        pad = [(0, s - d) for s, d in zip(shape, a.shape)]
        out.append(np.pad(a, pad))
    return jnp.asarray(np.stack(out))


def _ragged_policy(compact: bool, chunk, mesh, buckets, guaranteed: bool):
    """Map the legacy ragged keyword surface onto a DispatchPolicy."""
    from .api import DispatchPolicy

    return DispatchPolicy.from_legacy(compact, mesh, chunk=chunk,
                                      buckets=buckets,
                                      guaranteed=guaranteed)


def solve_ot_ragged(
    instances,
    eps,
    *,
    buckets: Sequence[int] = DEFAULT_BUCKETS,
    guaranteed: bool = False,
    compact: bool = True,
    chunk: int | None = None,
    mesh=None,
):
    """Solve a ragged list of ``(c, nu, mu)`` OT instances via bucketed
    batched dispatch. Returns per-instance dicts (in input order) with the
    unpadded plan and scalar diagnostics.

    ``compact=True`` (default) routes each bucket through the convergence-
    compacting driver (core/compaction.py): converged instances retire
    between k-phase dispatches instead of riding lockstep until the slowest
    one finishes, and ``eps`` may be a per-instance sequence. ``compact=
    False`` restores the PR-1 lockstep dispatch (results are identical;
    mixed-eps sets are sub-grouped by eps value per bucket). Tradeoff:
    compaction wins on convergence-skewed buckets (2-4x on the in-repo
    bench) but its per-chunk converged-mask sync can lose ~20-50% on tiny
    or convergence-uniform buckets — pass ``compact=False`` there.

    ``mesh`` (a 1-D batch mesh, see ``launch.mesh.make_batch_mesh``)
    dispatches each bucket through the mesh-distributed compacting driver
    (core/distributed.py) — same results, batch axis sharded across
    devices. Requires ``compact=True``.

    Thin wrapper over ``core/api.solve(OT, ...)``."""
    from .api import solve
    from .problem import OT

    return solve(OT, instances, eps,
                 _ragged_policy(compact, chunk, mesh, buckets, guaranteed))


def solve_assignment_ragged(
    cs,
    eps,
    *,
    buckets: Sequence[int] = DEFAULT_BUCKETS,
    guaranteed: bool = False,
    compact: bool = True,
    chunk: int | None = None,
    mesh=None,
):
    """Solve a ragged list of assignment cost matrices via bucketed batched
    dispatch. Returns per-instance dicts (in input order). ``compact`` and
    ``mesh`` as in ``solve_ot_ragged``. Thin wrapper over
    ``core/api.solve(ASSIGNMENT, ...)``."""
    from .api import solve
    from .problem import ASSIGNMENT

    return solve(ASSIGNMENT, cs, eps,
                 _ragged_policy(compact, chunk, mesh, buckets, guaranteed))
