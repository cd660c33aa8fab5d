"""Convergence-compacting chunked-phase batch driver, generic over a
:class:`~repro.core.problem.ProblemSpec`.

The lockstep batched solvers (core/batched.py) vmap one unbounded
``lax.while_loop`` over the batch, so every instance in a bucket burns
phase-iterations until the *slowest* instance converges — ROADMAP measured
~3x max-phase skew at eps=0.1, i.e. most batched FLOPs were select-masked
no-ops. This driver recovers the paper's per-instance O(log n / eps^2)
parallel bound for a fleet of instances by retiring converged work early:

  1. dispatch ``k`` phases to the whole bucket via the resumable stepped
     cores (``spec.run_phases``);
  2. fetch the (B,) converged mask (one scalar-per-instance device->host
     sync per chunk — the phase loops themselves never sync);
  3. once occupancy has halved, scatter the bucket's states into a full-B
     result buffer and gather the survivors into the next power-of-two
     batch bucket (converged instances pad the gather; their termination
     predicate is already false, so they add zero loop iterations);
  4. when everyone has terminated, run the completion/cost epilogue ONCE,
     in bulk, over the full-B buffer of retired states.

The driver is written once: ``solve_compacting(spec, ...)`` takes any
ProblemSpec (``ASSIGNMENT`` or ``OT`` from core/problem.py) and never
mentions either problem by name. The public per-problem entry points
(``solve_assignment_batched_compacting`` / ``solve_ot_batched_compacting``)
are thin spec-binding wrappers with their original signatures.

Every dispatched program is keyed by (bucket shape, k, batch bucket), so
the power-of-two descent B -> B/2 -> ... compiles each size once and
reuses it for all future traffic. Per-instance state trajectories are
bit-identical to the lockstep path (and hence to unbatched solves): the
chunked loops share the exact phase body, vmap lanes never interact, and
the deterministic proposal hash keys depend only on the within-instance
(row, col, phase) — never on batch position. Retiring a neighbor cannot
perturb a survivor.

Unlike the lockstep path, ``eps`` may be a per-instance (B,) array here:
the rounding prologue takes eps as a traced scalar and the termination
threshold/phase cap are per-instance anyway, so one compacted dispatch can
serve a mixed-accuracy batch (the skew such mixtures create is exactly
what compaction absorbs).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import lru_cache
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# the serving stack's one monotonic clock (repro.obs.metrics.now): chunk
# timing and deadline checks here share a time base with the scheduler's
# spans, submit timestamps, and per-request deadlines
from ..obs.metrics import now as _now
from ..obs.tracing import region
from .problem import ASSIGNMENT, OT, lane_map, pow2_at_least

DEFAULT_CHUNK = 8


@dataclass
class CompactionStats:
    """Occupancy/waste accounting for one compacted solve."""
    batch: int                 # real instances
    dispatched_batch: int      # power-of-two padded batch the driver ran
    chunk: int                 # k, phases per dispatch
    dispatches: int = 0
    # (batch bucket, live instances) after each k-phase dispatch
    occupancy: List[Tuple[int, int]] = field(default_factory=list)
    slot_phases: int = 0       # phase-slots actually executed (all lanes)
    phases_needed: int = 0     # sum of per-instance converged phase counts
    lockstep_slot_phases: int = 0  # batch * max(phases): what lockstep burns
    # final integer solver state (trimmed to the real batch), stashed only
    # when the solver is called with ``keep_state=True`` so the feasibility
    # certificates (core/feasibility.py) can run on the exact
    # pre-completion state (BatchedAssignmentResult carries no state; the
    # OT result's ``state`` field already does). Not serialized.
    final_state: Optional[Any] = None
    # wall-clock deadline support: ``deadline_hit`` records that the chunk
    # loop stopped dispatching because its next chunk would overrun the
    # caller's budget; ``unconverged`` is the (dispatched_batch,) bool mask
    # of lanes (original batch order) whose termination predicate had not
    # yet fired at the cut — their answers are best-so-far (still
    # primal-feasible with eps-feasible duals; see Solution.degraded).
    deadline_hit: bool = False
    unconverged: Optional[Any] = None

    def as_dict(self) -> dict:
        return {
            "batch": self.batch,
            "dispatched_batch": self.dispatched_batch,
            "chunk": self.chunk,
            "dispatches": self.dispatches,
            "occupancy": [list(o) for o in self.occupancy],
            "slot_phases": self.slot_phases,
            "phases_needed": self.phases_needed,
            "lockstep_slot_phases": self.lockstep_slot_phases,
            "deadline_hit": self.deadline_hit,
        }


@jax.jit
def _gather(tree, idx):
    return jax.tree_util.tree_map(lambda a: a[idx], tree)


@jax.jit
def _scatter(buf, tree, idx):
    return jax.tree_util.tree_map(lambda b, a: b.at[idx].set(a), buf, tree)


def _drive(data, state, run_fn, conv_fn, max_chunks: int,
           stats: CompactionStats, deadline: Optional[float] = None,
           obs=None):
    """Generic compacting loop over a per-instance ``data`` pytree (solver
    inputs: integer costs, thresholds, caps) and a solver-state pytree.

    ``run_fn(data, state) -> state`` advances every lane by at most
    ``stats.chunk`` phases (the chunk size is baked into ``run_fn``) and
    DONATES the state buffers (re-dispatch never holds two copies of the
    solver state in device memory); ``conv_fn(data, state) ->
    ((B,) bool, (B,) int32)`` is the per-lane termination predicate
    bundled with the per-lane phase counters. Returns the full-size state
    pytree with every lane terminated, in original batch order.

    The ``conv, ph = jax.device_get(...)`` fetch is the ONLY device->host
    sync in the loop (one per chunk) — the phase counters ride the same
    dispatch as the mask precisely so they don't cost a second blocking
    fetch. ``repro.analysis``'s hot-loop sync audit pins this contract.

    ``deadline`` is an absolute ``time.monotonic()`` instant: after each
    chunk the driver compares the host clock (free — the conv fetch
    already synced) plus the measured duration of the chunk that just ran
    against it, and stops dispatching when the NEXT chunk would overrun,
    flushing best-so-far state and recording the still-unconverged lanes
    on ``stats``. At least one chunk always runs (progress guarantee).

    ``obs`` is an optional event emitter (duck-typed
    ``repro.obs.Tracer``): one ``"chunk"`` event per dispatch carrying
    the batch bucket, live-lane count, wall time, max phase delta, the
    chunk program's jit-cache delta (nonzero exactly when this dispatch
    compiled) and ``rebucket_s``, the host seconds of the re-bucket that
    ran since the previous chunk event (0.0 if none: the event stays
    right after the conv fetch, so a re-bucket rides on the next one),
    plus a ``"deadline-cut"`` event when the budget stops the loop. Each
    chunk, launch through the conv fetch, is also a ``solve.chunk``
    region on the profiler's host timeline (``repro.obs.region``; the
    ``"chunk"`` event is its record for the sinks), and each re-bucket,
    flush through the survivor gather, a ``solve.rebucket`` region.
    Everything emitted is a host scalar the loop already had —
    observability adds no device->host syncs (the sync audit holds this
    loop to the single conv fetch either way)."""
    idx = np.arange(stats.dispatched_batch)
    cache_fn = getattr(run_fn, "_cache_size", None) if obs is not None \
        else None
    cache_prev = cache_fn() if cache_fn is not None else 0
    # The result buffer is born at the FIRST flush (where ``idx`` is still
    # the identity, so the flush is just the current state) rather than
    # aliasing the initial state: run_fn donates its state argument, and a
    # buffer that aliased the donated initial state would be dead here.
    buf = None
    cur_d, cur_s = data, state
    ph_prev = np.zeros((stats.dispatched_batch,), np.int64)
    rebucket_s = 0.0
    for _ in range(max_chunks):
        with region(obs, "solve.chunk", record=False):
            t_chunk = _now()
            cur_s = run_fn(cur_d, cur_s)
            stats.dispatches += 1
            conv, ph = jax.device_get(conv_fn(cur_d, cur_s))
            t_chunk = _now() - t_chunk
        ph = ph.astype(np.int64)
        bb = int(conv.shape[0])
        # the vmapped while_loop runs every lane for the max phase delta
        dph = int((ph - ph_prev).max(initial=0))
        stats.slot_phases += bb * dph
        ph_prev = ph
        live = int((~conv).sum())
        stats.occupancy.append((bb, live))
        if obs is not None:
            cache_now = cache_fn() if cache_fn is not None else 0
            obs.event("chunk", bucket=bb, live=live, chunk_s=t_chunk,
                      phases=dph, compiled=cache_now - cache_prev,
                      rebucket_s=rebucket_s)
            cache_prev = cache_now
        rebucket_s = 0.0
        if live == 0:
            buf = cur_s if buf is None else _scatter(buf, cur_s,
                                                     jnp.asarray(idx))
            break
        if deadline is not None and _now() + t_chunk >= deadline:
            # the earliest deadline is at risk: another chunk (estimated
            # by the one that just ran) would overrun it. Flush best-so-
            # far state and mark the lanes that had not yet terminated —
            # the epilogue is well-defined on any phase boundary (the
            # phase-cap termination path already runs it on unconverged
            # states), so callers get a primal-feasible answer whose
            # certificate reports the true (larger) gap.
            stats.deadline_hit = True
            un = np.zeros((stats.dispatched_batch,), bool)
            un[idx[~conv]] = True
            stats.unconverged = un
            if obs is not None:
                obs.event("deadline-cut", bucket=bb, live=live)
            buf = cur_s if buf is None else _scatter(buf, cur_s,
                                                     jnp.asarray(idx))
            break
        nb = pow2_at_least(live)
        if nb <= bb // 2:
            # retire: flush ALL current lanes to the result buffer (the
            # survivor writes are dead — overwritten by a later flush —
            # but a full-lane scatter keeps the index vector at the fixed
            # bucket length, so the program set stays one-per-(shape, B);
            # scattering only the converged lanes would retrace per
            # data-dependent lane count), then gather survivors (padded
            # with one converged lane, which is inert — its predicate is
            # already false) into the next bucket.
            with region(obs, "solve.rebucket", record=False):
                t_re = _now()
                buf = cur_s if buf is None else _scatter(buf, cur_s,
                                                         jnp.asarray(idx))
                surv = np.flatnonzero(~conv)
                fill = np.flatnonzero(conv)[:1]
                sel = np.concatenate([surv, np.repeat(fill, nb - live)])
                sel_j = jnp.asarray(sel)
                cur_d = _gather(cur_d, sel_j)
                cur_s = _gather(cur_s, sel_j)
                rebucket_s = _now() - t_re
            idx = idx[sel]
            ph_prev = ph[sel]
    else:
        # phase caps bound every lane, so the loop always breaks; flush
        # defensively if a cap change ever violates that.
        buf = cur_s if buf is None else _scatter(buf, cur_s,
                                                 jnp.asarray(idx))
    return buf


# --------------------------------------------------------------------------
# One jitted function family per (spec, k) — shared with the collapsed
# single-device tail of the distributed driver.
# --------------------------------------------------------------------------

STAGES = ("prologue", "init", "chunk", "conv", "epilogue")


def stage_fns(spec, k: int, prefix: str = ""):
    """The spec's five per-solve functions (:data:`STAGES`) vmapped (the
    float epilogue lane-mapped, ``problem.lane_map``) over a batch, each
    named ``<spec.name>_<prefix><stage>``: jitted, a program is then the
    module ``jit_ot_chunk`` (``jit_ot_mesh_chunk`` for the mesh's
    ``prefix="mesh_"``) in an HLO dump and a device trace, where an
    anonymous lambda would be ``jit__lambda``. ``conv`` returns ``(mask,
    phases)`` in one program so the driver's per-chunk device->host sync
    fetches both in a single blocking transfer (the hot-loop sync audit
    in repro.analysis holds the loop to exactly that one fetch)."""
    def prologue(ops):
        return jax.vmap(spec.prologue)(ops)

    def init(data, ctx):
        return jax.vmap(spec.init_state)(data, ctx)

    def chunk(data, state):
        return jax.vmap(lambda d, s: spec.run_phases(d, s, k))(data, state)

    def conv(data, state):
        return jax.vmap(spec.converged)(data, state), state.phases

    fns = (prologue, init, chunk, conv, lane_map(spec.epilogue))
    for stage, fn in zip(STAGES, fns):
        fn.__name__ = fn.__qualname__ = f"{spec.name}_{prefix}{stage}"
    return fns


@lru_cache(maxsize=None)
def spec_fns(spec, k: int):
    """(prologue, init, chunk, conv, epilogue): :func:`stage_fns` jitted.
    The chunk dispatch donates the state buffers (one copy of solver
    state on device, not two)."""
    prologue, init, chunk, conv, epilogue = stage_fns(spec, k)
    return (jax.jit(prologue), jax.jit(init),
            jax.jit(chunk, donate_argnums=(1,)), jax.jit(conv),
            jax.jit(epilogue))


def max_chunk_dispatches(phase_cap: np.ndarray, k: int) -> int:
    """Upper bound on k-phase dispatches (phase caps bound every lane)."""
    return -(-int(phase_cap.max(initial=1)) // max(k, 1)) + 2


def solve_compacting(
    spec,
    inputs,
    eps,
    *,
    sizes=None,
    k: int = DEFAULT_CHUNK,
    guaranteed: bool = False,
    keep_state: bool = False,
    deadline: Optional[float] = None,
    obs=None,
    **prep_kw,
):
    """The generic compacting driver: solve a (B, M, N) batch of ``spec``
    instances with convergence compaction.

    Args:
      spec: a ProblemSpec (``ASSIGNMENT`` or ``OT`` from core/problem.py).
      inputs: dict of batched operands (``{"c": ...}`` for assignment,
        ``{"c": ..., "nu": ..., "mu": ...}`` for OT).
      eps: scalar, or (B,) per-instance array (mixed-accuracy batch — the
        lockstep path cannot express this).
      k: phases per dispatch; any value yields identical results.
      keep_state: stash the final pre-completion integer state on the
        returned stats (``final_state``) for feasibility certificates;
        off by default so serving paths don't retain an extra state copy.
      deadline: absolute monotonic-clock (``repro.obs.now``) budget; the
        chunk loop stops dispatching when the next chunk would overrun it
        and returns best-so-far answers (``stats.deadline_hit`` /
        ``unconverged``).
      obs: optional event emitter (``repro.obs.Tracer``): per-chunk
        ``"chunk"`` events (bucket, live, wall time, phase delta,
        jit-cache delta) and ``"deadline-cut"`` — see :func:`_drive` —
        and a ``solve.prepare`` region (``repro.obs.region``) over the
        host prep up to the prologue launch.
      prep_kw: spec-specific prep options (OT: ``theta``).

    Returns ``(result, CompactionStats)``; every result leaf is
    bit-identical per instance to the lockstep path (and to the unbatched
    solver) for a shared scalar eps.
    """
    with region(obs, "solve.prepare"):
        inputs = spec.canonicalize(inputs)
        b, m, n = spec.batch_shape(inputs)
        if b == 0:
            return (spec.empty_result(m, n),
                    CompactionStats(batch=0, dispatched_batch=0, chunk=k))
        # Pad the batch to a power of two with born-converged empty
        # instances, so the descent B -> B/2 -> ... visits only
        # power-of-two shapes.
        p = spec.prepare(inputs, eps, sizes=sizes, guaranteed=guaranteed,
                         **prep_kw)
        if _audit_debug_checks():
            # Sanitizer mode: checkify-instrumented (nan/index/div +
            # solver invariants) variants of the dispatched programs.
            # Slower (no donation, per-chunk error sync) — never on by
            # default.
            from ..analysis.checkified import checkified_spec_fns
            prologue, init, chunk, conv, epilogue = checkified_spec_fns(
                spec, k)
        else:
            prologue, init, chunk, conv, epilogue = spec_fns(spec, k)
        ops = {kk: jnp.asarray(v) for kk, v in p.ops.items()}
    data, ctx = prologue(ops)
    # epilogue operands the prologue does not transform are taken straight
    # from ops (outside the jit), not round-tripped through it — a
    # pass-through output would materialize a second device copy of the
    # (bp, M, N) operands
    ctx = {**ctx, **{kk: ops[kk] for kk in spec.ctx_ops}}
    state0 = init(data, ctx)
    stats = CompactionStats(batch=b, dispatched_batch=p.bp, chunk=k)
    final = _drive(data, state0, chunk, conv,
                   max_chunk_dispatches(p.phase_cap, k), stats,
                   deadline=deadline, obs=obs)
    r = epilogue(ctx, final)

    phases = np.asarray(final.phases[:b], np.int64)
    stats.phases_needed = int(phases.sum())
    stats.lockstep_slot_phases = b * int(phases.max(initial=0))
    if keep_state:
        stats.final_state = jax.tree_util.tree_map(lambda a: a[:b], final)
    return spec.trim(r, b), stats


# --------------------------------------------------------------------------
# Spec-binding wrappers (original public entry points, unchanged contracts)
# --------------------------------------------------------------------------

def solve_assignment_batched_compacting(
    c: jnp.ndarray,
    eps,
    *,
    sizes=None,
    k: int = DEFAULT_CHUNK,
    guaranteed: bool = False,
    keep_state: bool = False,
):
    """Compacting counterpart of ``solve_assignment_batched``; binds
    ``ASSIGNMENT`` to :func:`solve_compacting` (see there for the
    contract). Returns ``(BatchedAssignmentResult, CompactionStats)``."""
    return solve_compacting(ASSIGNMENT, {"c": c}, eps, sizes=sizes, k=k,
                            guaranteed=guaranteed, keep_state=keep_state)


def solve_ot_batched_compacting(
    c: jnp.ndarray,
    nu: jnp.ndarray,
    mu: jnp.ndarray,
    eps,
    *,
    sizes=None,
    theta=None,
    k: int = DEFAULT_CHUNK,
    guaranteed: bool = False,
    keep_state: bool = False,
):
    """Compacting counterpart of ``solve_ot_batched``; binds ``OT`` to
    :func:`solve_compacting`. Same contract as the lockstep path
    ((B, M, N) costs, (B, M)/(B, N) masses, padding masked from ``sizes``
    inside the programs), plus per-instance ``eps`` support. Returns
    ``(OTResult with leading batch axes, CompactionStats)``."""
    return solve_compacting(OT, {"c": c, "nu": nu, "mu": mu}, eps,
                            sizes=sizes, k=k, guaranteed=guaranteed,
                            keep_state=keep_state, theta=theta)


# --------------------------------------------------------------------------
# repro.analysis registration: the vmapped chunk/conv dispatches are the
# programs the compacting loop actually re-issues per bucket, so they are
# what the donation-safety and dtype-drift rules must see.
# --------------------------------------------------------------------------

from ..analysis import registry as _audit  # noqa: E402
from ..analysis import debug_checks_enabled as _audit_debug_checks  # noqa: E402


def _tiny_batch(spec_name: str):
    """A deterministic (2, 4, 4) prepared batch for tracing dispatches."""
    spec = ASSIGNMENT if spec_name == "assignment" else OT
    b, mn = 2, 4
    c = np.linspace(0.0, 1.0, b * mn * mn, dtype=np.float32)
    inputs = {"c": c.reshape(b, mn, mn)}
    if spec_name == "ot":
        inputs["nu"] = np.full((b, mn), 1.0 / mn, np.float32)
        inputs["mu"] = np.full((b, mn), 1.0 / mn, np.float32)
    p = spec.prepare(spec.canonicalize(inputs), 0.25)
    prologue, init, chunk, conv, _ = spec_fns(spec, 2)
    ops = {kk: jnp.asarray(v) for kk, v in p.ops.items()}
    data, ctx = prologue(ops)
    state = init(data, ctx)
    return chunk, conv, data, state


def _trace_chunk(spec_name: str):
    chunk, _, data, state = _tiny_batch(spec_name)
    return _audit.trace_entry(
        name=f"core.compaction.chunk[{spec_name}]",
        fn=chunk,
        args={"data": data, "state": state},
        donated={"state"},
        tags={"chunk-dispatch", spec_name},
        source=__name__,
    )


def _trace_conv(spec_name: str):
    _, conv, data, state = _tiny_batch(spec_name)
    return _audit.trace_entry(
        name=f"core.compaction.conv[{spec_name}]",
        fn=conv,
        args={"data": data, "state": state},
        tags={"conv-dispatch", spec_name},
        source=__name__,
    )


_audit.register("core.compaction.chunk[assignment]",
                lambda: _trace_chunk("assignment"), source=__name__)
_audit.register("core.compaction.chunk[ot]",
                lambda: _trace_chunk("ot"), source=__name__)
_audit.register("core.compaction.conv[assignment]",
                lambda: _trace_conv("assignment"), source=__name__)
_audit.register("core.compaction.conv[ot]",
                lambda: _trace_conv("ot"), source=__name__)
