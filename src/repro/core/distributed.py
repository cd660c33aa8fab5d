"""Mesh-distributed convergence-compacting batch dispatch, generic over a
:class:`~repro.core.problem.ProblemSpec`.

The paper's bound is *parallel* time O(log n / eps^2); PR 1/2 exploited it
within one device (vmapped batches, compacting phase dispatch) while
core/sharded.py exploited it across devices for ONE instance (row/col
matrix sharding). This module unifies the two: a fleet of instances is
sharded along the BATCH axis of a 1-D device mesh, each k-phase dispatch
runs the spec's resumable stepped core under ``shard_map`` with every
operand placed ``NamedSharding(P(batch_axis))``, and the compacting driver
retires converged instances across the global batch between dispatches.
Each device runs its own vmapped phase loop over its local lanes — no
cross-device traffic inside a dispatch, so per-device lockstep waste is
bounded by the LOCAL max phase count, not the global one.

Like core/compaction.py, the driver exists ONCE: ``solve_mesh(spec, ...)``
and the generic matrix-placement loop are problem-agnostic; the public
``solve_assignment_distributed`` / ``solve_ot_distributed`` entry points
are thin spec bindings with their original signatures.

Device-put / re-bucketing policy (the distributed analogue of the
power-of-two bucket descent in core/compaction.py):

  * the dispatched batch starts at ``max(pow2_at_least(B), D)`` where
    ``D`` is the (power-of-two) device count along the batch axis, so the
    batch axis is always divisible by the mesh;
  * between dispatches the (B,) converged mask is fetched with one global
    gather; when occupancy has halved, ALL lanes are flushed into the
    full-size sharded result buffer (each device copies its own lanes out
    of the replicated bucket, see ``_place_into``) and the survivors are
    gathered and EXPLICITLY ``device_put`` onto the next power-of-two
    bucket's ``NamedSharding(P(batch_axis))`` — re-bucketing is a
    host-driven re-shard, never an implicit layout change;
  * once the next bucket would drop below the device count
    (``pow2_at_least(live) < D``), the surviving lanes are collapsed onto
    a single device (replicated single-device dispatch) and the remaining
    descent continues exactly as the single-device compacting driver —
    a 2-lane tail is latency-bound, not throughput-bound, and spreading
    it over the mesh would only add dispatch overhead;
  * batches smaller than the mesh floor to begin with skip the mesh
    entirely and run the single-device driver.

A placement policy (``choose_placement``) picks per bucket between this
batch-axis sharding (many small instances) and the row/col MATRIX sharding
of core/sharded.py (few large instances, where batch sharding would leave
most of the mesh idle).

Under batch placement, per-lane results are BIT-IDENTICAL to the
single-device compacting driver (and hence to lockstep batched and
unbatched solves): shard_map lanes never interact, the proposal hash keys
depend only on the within-instance (row, col, phase), retirement/re-sharding
of a neighbor cannot perturb a survivor, and each device runs the float
epilogue lane by lane (``problem.lane_map``, as the compacting driver does),
so a lane's plan does not depend on how many lanes share its device. ``eps``
may be a per-instance (B,) array, as in the compacting driver. Under
matrix placement each instance solves at its own mesh-divisible padded
shape, so the INTEGER state (matching, duals, flows, phase counts) is
bit-identical but the float epilogue (plan/cost sums) may differ from the
batch-placement value by reassociation ulps (~1e-9 relative) — the same
caveat as any shape change of an XLA float reduction.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import lru_cache
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .compaction import (
    DEFAULT_CHUNK,
    CompactionStats,
    _gather,
    max_chunk_dispatches,
    solve_compacting,
    spec_fns,
    stage_fns,
)
from .problem import (
    ASSIGNMENT,
    OT,
    _sizes_arrays,
    eps_array,
    pow2_at_least,
)
from ..obs.metrics import now as _now
from ..obs.tracing import region


@dataclass
class DistributedStats(CompactionStats):
    """CompactionStats plus mesh/placement accounting.

    ``slot_phases`` counts PER-DEVICE lockstep slots (each device's local
    vmapped loop runs its local lanes for the local max phase delta), so
    it is directly comparable with the single-device driver's number —
    the difference is the waste sharding itself removes."""
    devices: int = 1
    batch_axis: str = "data"
    placement: str = "batch"
    collapsed_at: Optional[int] = None      # bucket size at 1-device collapse
    devices_per_dispatch: List[int] = field(default_factory=list)

    def as_dict(self) -> dict:
        d = super().as_dict()
        d.update({
            "devices": self.devices,
            "batch_axis": self.batch_axis,
            "placement": self.placement,
            "collapsed_at": self.collapsed_at,
            "devices_per_dispatch": list(self.devices_per_dispatch),
        })
        return d


def choose_placement(b: int, m: int, n: int, n_devices: int,
                     *, matrix_min_size: int = 128) -> str:
    """Placement policy for one bucket: ``"batch"`` (shard the batch axis)
    vs ``"matrix"`` (row/col-shard each cost matrix, core/sharded.py).

    Batch sharding wins whenever there are enough instances to occupy the
    mesh (b >= devices) or the instances are too small for per-matrix
    collectives to pay off; matrix sharding wins for a few large
    instances, where batch sharding would leave most devices idle."""
    if n_devices <= 1 or b >= n_devices:
        return "batch"
    if min(m, n) >= matrix_min_size:
        return "matrix"
    return "batch"


def _require_pow2(d: int) -> None:
    if d & (d - 1):
        raise ValueError(
            f"batch-axis device count must be a power of two (got {d}); "
            "build the mesh with launch.mesh.make_batch_mesh"
        )


@lru_cache(maxsize=None)
def _matrix_mesh(mesh: Mesh) -> Tuple[Mesh, str, str]:
    """(mesh, row_axis, col_axis) for matrix placement: reuse a 2-D mesh's
    leading axes, or fold a 1-D batch mesh into the squarest (r, c) grid."""
    if len(mesh.axis_names) >= 2:
        return mesh, mesh.axis_names[0], mesh.axis_names[1]
    from ..launch.mesh import _make_mesh

    devs = list(mesh.devices.flat)
    d = len(devs)
    r = 1
    while r * 2 * r * 2 <= d:
        r *= 2
    return _make_mesh((r, d // r), ("data", "model"), devs), "data", "model"


# --------------------------------------------------------------------------
# shard_map-wrapped stepped core (one cache entry per (spec, mesh, axis, k))
# --------------------------------------------------------------------------

def _wrap(mesh: Mesh, axis: str, fn, donate=()):
    spec = P(axis)
    return jax.jit(
        jax.shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec),
        donate_argnums=donate,
    )


@lru_cache(maxsize=None)
def _mesh_fns(spec, mesh: Mesh, axis: str, k: int):
    """(prologue, init, chunk, conv, epilogue): the spec's per-instance
    stepped-core functions (``compaction.stage_fns``, named
    ``<spec.name>_mesh_<stage>``) vmapped over the local batch shard and
    shard_map'ed over the mesh. Every operand/result is placed
    ``NamedSharding(P(axis))``; the chunk dispatch donates the state."""
    prologue, init, chunk, conv, epilogue = stage_fns(spec, k, "mesh_")
    return (_wrap(mesh, axis, prologue),
            jax.jit(init, out_shardings=NamedSharding(mesh, P(axis))),
            _wrap(mesh, axis, chunk, donate=(1,)),
            _wrap(mesh, axis, conv),
            _wrap(mesh, axis, epilogue))


@lru_cache(maxsize=None)
def _place_into(mesh: Mesh, axis: str):
    """Write a bucket's lanes back into the full-size sharded result
    buffer: ``buf[j] = tree[pos[j]]`` wherever ``pos[j] >= 0``. The bucket
    arrives replicated and each device picks its own lanes with a local
    gather, so no partitioned scatter runs: on four TPU v5e chips the
    partitioned scatter of a bucket into the sharded buffer corrupted the
    duals and free masses of some lanes."""
    def place(buf, tree, pos):
        def one(b, a):
            keep = (pos >= 0).reshape(pos.shape + (1,) * (b.ndim - 1))
            return jnp.where(keep, a[jnp.maximum(pos, 0)], b)

        return jax.tree_util.tree_map(one, buf, tree)

    return jax.jit(jax.shard_map(place, mesh=mesh,
                                 in_specs=(P(axis), P(), P(axis)),
                                 out_specs=P(axis)))


def _put(tree, target):
    return jax.tree_util.tree_map(lambda a: jax.device_put(a, target), tree)


# --------------------------------------------------------------------------
# The distributed compacting drive
# --------------------------------------------------------------------------

def _drive_distributed(data, state, run_s, conv_s, run_1, conv_1,
                       max_chunks: int, stats: DistributedStats,
                       mesh: Mesh, axis: str,
                       deadline: Optional[float] = None, obs=None):
    """Mesh counterpart of compaction._drive. ``data``/``state`` arrive
    device_put onto ``NamedSharding(mesh, P(axis))``; ``run_s``/``conv_s``
    are the shard_map'ed chunk/converged dispatches and ``run_1``/``conv_1``
    the single-device ones used after the collapse. Chunk dispatches donate
    the state buffers (one copy of solver state per bucket, not two).
    ``deadline`` is an absolute monotonic (``repro.obs.now``) budget with
    the same best-so-far cut semantics as compaction._drive. ``obs`` is
    the same optional per-chunk event emitter as compaction._drive, with
    the same ``solve.chunk`` and ``solve.rebucket`` regions and
    ``rebucket_s`` (here the re-bucket is the flush, the survivor gather
    and the re-shard or the collapse); the ``"chunk"`` events also carry
    the device count this dispatch ran on and ``chip_phases``, each
    device's local max phase delta (the slowest sets ``phases``, and the
    others wait for it). Events are host values the loop already had — no
    extra device syncs."""
    d0 = int(mesh.shape[axis])
    cache_fns = ({id(run_s): getattr(run_s, "_cache_size", None),
                  id(run_1): getattr(run_1, "_cache_size", None)}
                 if obs is not None else {})
    cache_prev = {k: (f() if f is not None else 0)
                  for k, f in cache_fns.items()}
    sh = NamedSharding(mesh, P(axis))
    sh_rep = NamedSharding(mesh, P())
    dev0 = next(iter(mesh.devices.flat))
    idx = np.arange(stats.dispatched_batch)
    buf = None          # born at the first flush (state is donated; see
                        # compaction._drive for the aliasing argument)
    cur_d, cur_s = data, state
    sharded = d0 > 1

    def flush(buf, tree, idx):
        if buf is None:
            # first flush: idx is still the identity, buf IS the state
            return tree
        # buffer lane -> bucket lane (filler duplicates hold one converged
        # lane's state, so any of them will do)
        pos = np.full((stats.dispatched_batch,), -1, np.int32)
        pos[idx] = np.arange(idx.shape[0], dtype=np.int32)
        return _place_into(mesh, axis)(buf, _put(tree, sh_rep),
                                       jax.device_put(pos, sh))

    ph_prev = np.zeros((stats.dispatched_batch,), np.int64)
    rebucket_s = 0.0
    for _ in range(max_chunks):
        with region(obs, "solve.chunk", record=False):
            t_chunk = _now()
            run_fn = run_s if sharded else run_1
            cur_s = run_fn(cur_d, cur_s)
            stats.dispatches += 1
            # global converged-mask + phase-counter gather: ONE (B,)
            # device->host sync per chunk (conv bundles both outputs, so
            # the phase counters don't cost a second blocking fetch — the
            # repro.analysis hot-loop sync audit pins this)
            conv, ph = jax.device_get((conv_s if sharded else conv_1)(
                cur_d, cur_s))
            t_chunk = _now() - t_chunk
        ph = ph.astype(np.int64)
        bb = int(conv.shape[0])
        d_now = d0 if sharded else 1
        stats.devices_per_dispatch.append(d_now)
        # per-device lockstep accounting: each device's vmapped while_loop
        # runs its local lanes for the LOCAL max phase delta
        chip_phases = (ph - ph_prev).reshape(d_now, bb // d_now).max(axis=1)
        stats.slot_phases += int(chip_phases.sum()) * (bb // d_now)
        ph_prev = ph
        live = int((~conv).sum())
        stats.occupancy.append((bb, live))
        if obs is not None:
            cf = cache_fns.get(id(run_fn))
            cache_now = cf() if cf is not None else 0
            obs.event("chunk", bucket=bb, live=live, chunk_s=t_chunk,
                      phases=int(chip_phases.max()),
                      devices=d_now, chip_phases=chip_phases.tolist(),
                      compiled=cache_now - cache_prev.get(id(run_fn), 0),
                      rebucket_s=rebucket_s)
            cache_prev[id(run_fn)] = cache_now
        rebucket_s = 0.0
        if live == 0:
            buf = flush(buf, cur_s, idx)
            break
        if deadline is not None and _now() + t_chunk >= deadline:
            # earliest deadline at risk: stop dispatching, flush best-so-
            # far state, and mark the unconverged lanes (original batch
            # order) — same cut semantics as compaction._drive
            stats.deadline_hit = True
            un = np.zeros((stats.dispatched_batch,), bool)
            un[idx[~conv]] = True
            stats.unconverged = un
            if obs is not None:
                obs.event("deadline-cut", bucket=bb, live=live)
            buf = flush(buf, cur_s, idx)
            break
        nb = pow2_at_least(live)
        if nb <= bb // 2:
            # flush ALL lanes (fixed-length scatter; see compaction._drive),
            # then gather survivors + one inert converged filler lane and
            # re-bucket under the explicit device-put policy.
            with region(obs, "solve.rebucket", record=False):
                t_re = _now()
                buf = flush(buf, cur_s, idx)
                surv = np.flatnonzero(~conv)
                fill = np.flatnonzero(conv)[:1]
                sel = np.concatenate([surv, np.repeat(fill, nb - live)])
                sel_j = jnp.asarray(sel)
                cur_d = _gather(cur_d, sel_j)
                cur_s = _gather(cur_s, sel_j)
                if sharded and nb < d0:
                    # below the mesh floor: replicated single-device dispatch
                    cur_d = _put(cur_d, dev0)
                    cur_s = _put(cur_s, dev0)
                    sharded = False
                    stats.collapsed_at = nb
                elif sharded:
                    # explicit re-shard of the shrunken bucket across the mesh
                    cur_d = _put(cur_d, sh)
                    cur_s = _put(cur_s, sh)
                rebucket_s = _now() - t_re
            idx = idx[sel]
            ph_prev = ph[sel]
    else:
        buf = flush(buf, cur_s, idx)
    return buf


# --------------------------------------------------------------------------
# The generic distributed entry point
# --------------------------------------------------------------------------

def _resolve_mesh(mesh, batch_axis):
    if mesh is None:
        from ..launch.mesh import make_batch_mesh

        mesh = make_batch_mesh(axis=batch_axis)
    d = int(mesh.shape[batch_axis])
    _require_pow2(d)
    return mesh, d


def solve_mesh(
    spec,
    inputs,
    eps,
    mesh: Mesh | None = None,
    *,
    sizes=None,
    k: int = DEFAULT_CHUNK,
    guaranteed: bool = False,
    batch_axis: str = "data",
    placement: str = "auto",
    keep_state: bool = False,
    deadline: Optional[float] = None,
    obs=None,
    **prep_kw,
):
    """Mesh-distributed counterpart of ``compaction.solve_compacting`` —
    same contract (spec + batched input dict, scalar or (B,) eps), same
    bit-identical per-instance results, with the batch axis sharded across
    ``mesh`` (built by ``launch.mesh.make_batch_mesh`` when None).
    ``placement`` is "auto" (``choose_placement``), "batch", or "matrix".
    ``keep_state`` stashes the pre-completion integer state on the stats
    for feasibility certificates (batch placement only — the matrix path's
    epilogue consumes the state, so the combination raises).
    ``deadline`` (absolute monotonic, ``repro.obs.now``) gives the chunk
    loop a wall-clock budget with best-so-far cut semantics (see
    ``solve_compacting``); matrix placement solves instance-by-instance
    with no chunk loop to cut, so it ignores the budget (best-effort).
    ``obs`` threads a per-chunk event emitter into the drive and marks
    the host prep as ``solve.prepare`` regions (see
    ``solve_compacting``); matrix placement emits no chunk events.

    Returns ``(result, DistributedStats)``."""
    with region(obs, "solve.prepare"):
        inputs = spec.canonicalize(inputs)
        b, m, n = spec.batch_shape(inputs)
        mesh, d = _resolve_mesh(mesh, batch_axis)
        mode = (choose_placement(b, m, n, d) if placement == "auto"
                else placement)
    if mode == "matrix" and b > 0:
        if keep_state and not getattr(spec, "state_on_result", False):
            # the matrix path discards the per-instance integer state
            # (the sharded epilogue consumes it) unless the spec's result
            # carries it (OT does); fail loudly rather than hand back
            # final_state=None
            raise ValueError("keep_state=True requires batch placement "
                             "(pass placement='batch')")
        return _solve_matrix(spec, inputs, eps, mesh, sizes, guaranteed,
                             k, batch_axis, **prep_kw)
    if b == 0 or pow2_at_least(b) < d:
        # below the mesh floor from the start: single-device dispatch
        out, cst = solve_compacting(
            spec, inputs, eps, sizes=sizes, k=k, guaranteed=guaranteed,
            keep_state=keep_state, deadline=deadline, obs=obs, **prep_kw)
        stats = _wrap_stats(cst, d, batch_axis, collapsed_at=cst.
                            dispatched_batch or None)
        return out, stats

    with region(obs, "solve.prepare"):
        p = spec.prepare(inputs, eps, sizes=sizes, guaranteed=guaranteed,
                         min_batch=d, **prep_kw)
        sh = NamedSharding(mesh, P(batch_axis))
        prologue_s, init_s, chunk_s, conv_s, epilogue_s = _mesh_fns(
            spec, mesh, batch_axis, k)
        _, _, chunk_1, conv_1, _ = spec_fns(spec, k)
        # straight onto the mesh; dropping ``p`` frees the lane-padded
        # copy of the operands (built when bp > b) on the default device
        # once the transfer is done
        ops = {kk: jax.device_put(v, sh) for kk, v in p.ops.items()}
        bp, phase_cap = p.bp, p.phase_cap
        del p
    data, ctx = prologue_s(ops)
    # verbatim epilogue operands come straight from the sharded ops (see
    # compaction.solve_compacting for the second-copy argument)
    ctx = {**ctx, **{kk: ops[kk] for kk in spec.ctx_ops}}
    state0 = init_s(data, ctx)
    stats = DistributedStats(batch=b, dispatched_batch=bp, chunk=k,
                             devices=d, batch_axis=batch_axis,
                             placement="batch")
    final = _drive_distributed(
        data, state0, chunk_s, conv_s, chunk_1, conv_1,
        max_chunk_dispatches(phase_cap, k), stats, mesh, batch_axis,
        deadline=deadline, obs=obs,
    )
    r = epilogue_s(ctx, final)

    phases = np.asarray(final.phases[:b], np.int64)
    stats.phases_needed = int(phases.sum())
    stats.lockstep_slot_phases = b * int(phases.max(initial=0))
    if keep_state:
        stats.final_state = jax.tree_util.tree_map(lambda a: a[:b], final)
    return spec.trim(r, b), stats


def _wrap_stats(cst: CompactionStats, devices: int, batch_axis: str,
                collapsed_at=None) -> DistributedStats:
    """Lift a single-device CompactionStats into DistributedStats (used
    when the whole solve ran below the mesh floor)."""
    st = DistributedStats(
        batch=cst.batch, dispatched_batch=cst.dispatched_batch,
        chunk=cst.chunk, dispatches=cst.dispatches,
        occupancy=cst.occupancy, slot_phases=cst.slot_phases,
        phases_needed=cst.phases_needed,
        lockstep_slot_phases=cst.lockstep_slot_phases,
        final_state=cst.final_state,
        deadline_hit=cst.deadline_hit, unconverged=cst.unconverged,
        devices=devices, batch_axis=batch_axis, placement="batch",
        collapsed_at=collapsed_at,
        devices_per_dispatch=[1] * cst.dispatches,
    )
    return st


# --------------------------------------------------------------------------
# Matrix placement: few large instances, row/col sharding per instance
# --------------------------------------------------------------------------

def _solve_matrix(spec, inputs, eps, mesh, sizes, guaranteed, k,
                  batch_axis, **prep_kw):
    """Generic matrix-placement loop: each instance padded up to
    mesh-divisible dims and solved row/col-sharded (core/sharded.py) via
    ``spec.matrix_instance``; ``spec.matrix_stack`` reassembles the
    batched result."""
    b, m, n = spec.batch_shape(inputs)
    m_valid, n_valid = _sizes_arrays(sizes, b, m, n)
    eps_arr = eps_array(eps, b, guaranteed)
    mesh2, row_axis, col_axis = _matrix_mesh(mesh)
    rdiv = int(mesh2.shape[row_axis])
    cdiv = int(mesh2.shape[col_axis])
    host = {kk: np.asarray(v) for kk, v in inputs.items()}
    rows = []
    for i in range(b):
        mi, ni = int(m_valid[i]), int(n_valid[i])
        mp = -(-mi // rdiv) * rdiv
        np_ = -(-ni // cdiv) * cdiv
        rows.append(spec.matrix_instance(
            host, i, mi, ni, mp, np_, float(eps_arr[i]), mesh2,
            row_axis, col_axis, **prep_kw))
    out = spec.matrix_stack(rows, m_valid, n_valid, m, n)
    stats = DistributedStats(
        batch=b, dispatched_batch=b, chunk=k,
        devices=int(np.prod(list(mesh2.shape.values()))),
        batch_axis=batch_axis, placement="matrix", dispatches=b)
    phases = np.asarray(out.phases, np.int64)
    stats.phases_needed = int(phases.sum())
    stats.lockstep_slot_phases = b * int(phases.max(initial=0))
    return out, stats


# --------------------------------------------------------------------------
# Spec-binding wrappers (original public entry points, unchanged contracts)
# --------------------------------------------------------------------------

def solve_assignment_distributed(
    c: jnp.ndarray,
    eps,
    mesh: Mesh | None = None,
    *,
    sizes=None,
    k: int = DEFAULT_CHUNK,
    guaranteed: bool = False,
    batch_axis: str = "data",
    placement: str = "auto",
    keep_state: bool = False,
):
    """Mesh-distributed counterpart of
    ``solve_assignment_batched_compacting``; binds ``ASSIGNMENT`` to
    :func:`solve_mesh` (see there for the contract). Returns
    ``(BatchedAssignmentResult, DistributedStats)``."""
    return solve_mesh(ASSIGNMENT, {"c": c}, eps, mesh, sizes=sizes, k=k,
                      guaranteed=guaranteed, batch_axis=batch_axis,
                      placement=placement, keep_state=keep_state)


def solve_ot_distributed(
    c: jnp.ndarray,
    nu: jnp.ndarray,
    mu: jnp.ndarray,
    eps,
    mesh: Mesh | None = None,
    *,
    sizes=None,
    theta=None,
    k: int = DEFAULT_CHUNK,
    guaranteed: bool = False,
    batch_axis: str = "data",
    placement: str = "auto",
):
    """Mesh-distributed counterpart of ``solve_ot_batched_compacting``;
    binds ``OT`` to :func:`solve_mesh` — same contract and bit-identical
    per-instance results. Returns ``(OTResult with leading batch axes,
    DistributedStats)``."""
    return solve_mesh(OT, {"c": c, "nu": nu, "mu": mu}, eps, mesh,
                      sizes=sizes, k=k, guaranteed=guaranteed,
                      batch_axis=batch_axis, placement=placement,
                      theta=theta)


# --------------------------------------------------------------------------
# repro.analysis registration: the shard_map'ed mesh chunk dispatch (the
# program `_drive_distributed` re-issues per bucket while sharded).
# --------------------------------------------------------------------------

from ..analysis import registry as _audit  # noqa: E402


def _trace_mesh_chunk(spec_name: str):
    from .compaction import _tiny_batch
    from ..launch.mesh import make_batch_mesh

    spec = ASSIGNMENT if spec_name == "assignment" else OT
    mesh = make_batch_mesh()
    _, _, chunk_s, _, _ = _mesh_fns(spec, mesh, "data", 2)
    _, _, data, state = _tiny_batch(spec_name)
    return _audit.trace_entry(
        name=f"core.distributed.mesh_chunk[{spec_name}]",
        fn=chunk_s,
        args={"data": data, "state": state},
        donated={"state"},
        tags={"mesh-dispatch", spec_name},
        source=__name__,
    )


_audit.register("core.distributed.mesh_chunk[assignment]",
                lambda: _trace_mesh_chunk("assignment"), source=__name__)
_audit.register("core.distributed.mesh_chunk[ot]",
                lambda: _trace_mesh_chunk("ot"), source=__name__)
