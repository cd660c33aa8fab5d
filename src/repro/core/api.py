"""The unified solve() front door: one entry point, every dispatch path.

``solve(spec, instances, eps, policy)`` routes any batch of assignment/OT
work — a ragged list of instances or one pre-batched bucket — through a
single code path to whichever driver the :class:`DispatchPolicy` selects:

  * ``lockstep``   the PR-1 fixed-shape vmapped while_loop (one phase-loop
                   dispatch, every lane runs until the slowest converges);
  * ``compact``    the convergence-compacting chunked-phase driver
                   (core/compaction.py) — per-instance eps supported;
  * ``mesh``       the mesh-distributed compacting driver
                   (core/distributed.py), with ``placement`` choosing
                   batch-axis sharding vs per-instance row/col matrix
                   sharding ("auto" applies ``choose_placement``).

Results are IDENTICAL across policies for the batch-sharded family
(lockstep == compact == mesh/batch, bit for bit); mesh/matrix matches to
reassociation ulps in the float epilogue (the documented shape caveat in
core/distributed.py).

Result surface — callers declare artifacts up front:

    sols = solve(OT, instances, eps, want=("cost", "duals"))
    sols[0].cost, sols[0].additive_gap()

``want=`` (a tuple of artifact names, also settable on the policy) makes
``solve`` return the typed Solution surface (core/solution.py): a
:class:`~repro.core.solution.SolutionBatch` for the pre-batched dict
form, a list of per-instance :class:`~repro.core.solution.Solution`
views for the ragged form. Artifacts are fetched device->host lazily and
at most once, so cost-only traffic moves O(B) scalars instead of the
O(B * m * n) dense plans; un-requested artifacts raise instead of
silently paying the bandwidth. With ``want=None`` (default) the legacy
surfaces are returned unchanged — ``(result, stats)`` for the dict form,
per-instance dicts for the ragged form — produced by a thin adapter over
the same Solution machinery, bit-identical to the historical values.

The serving layers (``OTService``, ``AsyncOTScheduler``) and the ragged
``solve_*_ragged`` wrappers all call this front door, so a new dispatch
strategy lands in exactly one place.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from ..obs.metrics import now as _now
from ..obs.tracing import region
from .compaction import DEFAULT_CHUNK, CompactionStats, solve_compacting
from .distributed import solve_mesh
from .problem import (  # noqa: F401  (re-exported: the front door and
    #   the specs it dispatches are one import site)
    ASSIGNMENT,
    FUSED_ASSIGNMENT,
    FUSED_OT,
    OT,
    fused_variant,
)
from .solution import Solution, SolutionBatch, SolveStats

_MODES = ("auto", "lockstep", "compact", "mesh")
_SOLVERS = ("pushrelabel", "sinkhorn", "hybrid", "auto")


@dataclass(frozen=True)
class DispatchPolicy:
    """How a batch should be dispatched.

    Args:
      mode: "auto" (mesh when ``mesh`` is set, else compact), "lockstep",
        "compact", or "mesh".
      mesh: 1-D batch mesh (``launch.mesh.make_batch_mesh``); required
        meaningfully only for mode="mesh" (None resolves the default
        host mesh there).
      placement: mesh-mode placement — "auto" | "batch" | "matrix".
      chunk: k, phases per dispatch of the compacting drivers.
      buckets: shape-bucket boundaries for ragged input (None -> the
        core/batched.py defaults; oversized shapes mint ceil-pow2
        buckets).
      guaranteed: run at eps/3 for the paper's <= OPT + eps*m bound.
      want: artifacts to expose on the typed Solution surface (e.g.
        ``("cost", "duals", "plan_sparse")``); None keeps the legacy
        return surface. ``solve(..., want=...)`` overrides this.
      validate: run the vectorized admission check (core/validate.py) on
        every dispatched bucket and raise
        :class:`~repro.core.validate.RequestRejected` naming the
        offending lanes before any solver program runs. The serving
        layers do their own per-request quarantine instead (reject one
        Future, keep the bucket); this flag is the all-or-nothing direct
        API equivalent.
      fused: run the k-phase loop through the fused Pallas phase kernel
        (``kernels/fused_phase``): slack + propose/accept + push +
        relabel in ONE kernel with the solver state resident in VMEM
        across all k phases, instead of the stepped
        ``slack_propose``-plus-XLA-update loop. Bit-identical results
        (asserted in tests/test_fused_phase.py); block sizes come from
        the backend table in ``kernels/ops.py``. Under mesh/matrix
        placement the per-instance row/col-sharded solve falls back to
        the stepped kernels (the fused kernel is a whole-instance
        program; sharding a single instance across devices is exactly
        the regime it cannot cover).
      solver: which ALGORITHM solves OT-family batches —
        "pushrelabel" (default: the paper's solver, guaranteed at every
        eps), "sinkhorn" (the log-domain AWR-scheduled spec in
        repro.portfolio — same additive-eps certificate, cheaper at
        loose eps), "hybrid" (coarse Sinkhorn duals warm-start the
        push-relabel finish; keeps the push-relabel guarantee), or
        "auto" (route per batch via the measured cost model,
        ``repro.portfolio.costmodel`` — deterministic for a loaded
        table, so an auto dispatch is bit-identical to naming its
        choice). Assignment batches ignore this knob (push-relabel is
        the only assignment solver). The chosen solver and the
        predicted-vs-actual wall cost land in ``SolveStats``.
    """
    mode: str = "auto"
    mesh: Any = None
    placement: str = "auto"
    chunk: Optional[int] = None
    buckets: Optional[Tuple[int, ...]] = None
    guaranteed: bool = False
    want: Optional[Tuple[str, ...]] = None
    validate: bool = False
    fused: bool = False
    solver: str = "pushrelabel"

    def __post_init__(self):
        if self.mode not in _MODES:
            raise ValueError(f"unknown dispatch mode {self.mode!r}; "
                             f"expected one of {_MODES}")
        if self.solver not in _SOLVERS:
            raise ValueError(f"unknown solver {self.solver!r}; "
                             f"expected one of {_SOLVERS}")
        if self.mode == "lockstep" and self.mesh is not None:
            raise ValueError("mode='lockstep' cannot dispatch over a mesh "
                             "— use mode='compact' or mode='mesh' (the "
                             "distributed driver is the compacting driver)")

    def resolved_mode(self) -> str:
        if self.mode != "auto":
            return self.mode
        return "mesh" if self.mesh is not None else "compact"

    @classmethod
    def from_legacy(cls, compact: bool, mesh=None, *, chunk=None,
                    buckets=None, guaranteed: bool = False,
                    placement: str = "auto",
                    want: Optional[Tuple[str, ...]] = None,
                    solver: str = "pushrelabel") -> "DispatchPolicy":
        """Map the legacy ``compact=``/``mesh=`` keyword surface
        (``solve_*_ragged``, ``OTService``) onto a policy — the ONE place
        that mapping and its mesh-requires-compact rule live."""
        if mesh is not None and not compact:
            raise ValueError("mesh dispatch requires compact=True (the "
                             "distributed driver is the compacting "
                             "driver)")
        mode = ("mesh" if mesh is not None
                else ("compact" if compact else "lockstep"))
        return cls(mode=mode, mesh=mesh, placement=placement, chunk=chunk,
                   buckets=None if buckets is None else tuple(buckets),
                   guaranteed=guaranteed,
                   want=None if want is None else tuple(want),
                   solver=solver)


def _resolve_solver(spec, policy: DispatchPolicy, inputs, eps):
    """(solver name, dispatch spec, predicted per-instance seconds) for
    ONE pre-batched bucket. Deterministic and side-effect free: calling
    it twice (the solve() wrapper does, to pick the Solution wrap spec)
    yields the same routing the dispatch took, so an "auto" result is
    bit-identical to naming the chosen solver. Only the OT family
    reroutes — assignment (and already-rerouted specs like the hybrid
    finish) pass through as push-relabel."""
    base = getattr(spec, "stepped", spec)
    if policy.solver == "pushrelabel" or base is not OT:
        return "pushrelabel", spec, None
    from .. import portfolio

    solver = policy.solver
    shape = np.shape(inputs["c"]) if isinstance(inputs, dict) else None
    n_eff = int(max(shape[1], shape[2])) if shape is not None else 0
    eps_min = float(np.min(np.asarray(eps, np.float64)))
    if solver == "auto":
        solver, predicted = portfolio.choose(n_eff, eps_min)
    else:
        model = portfolio.get_model()
        predicted = (None if model is None
                     else model.predict(solver, n_eff, eps_min))
    if solver == "sinkhorn":
        # stepped spec here; policy.fused upgrades it to the Pallas row
        # kernel downstream via fused_variant (the fused_spec hook)
        return "sinkhorn", portfolio.SINKHORN, predicted
    if solver == "hybrid":
        return "hybrid", spec, predicted
    return "pushrelabel", spec, predicted


def dispatch(
    spec,
    inputs: Dict[str, Any],
    eps,
    *,
    sizes=None,
    policy: Optional[DispatchPolicy] = None,
    keep_state: bool = False,
    deadline: Optional[float] = None,
    obs=None,
    **prep_kw,
):
    """Solve ONE pre-batched bucket (dict of (B, ...) operands) under
    ``policy``. Returns ``(result, stats)`` — ``stats`` is None for the
    plain lockstep path (it has no chunk/occupancy accounting),
    CompactionStats for compact (and for lockstep with
    ``keep_state=True``, which stashes the pre-completion state on a
    minimal stats object), DistributedStats for mesh. ``deadline`` is an
    absolute monotonic-clock (``repro.obs.now``) wall-clock budget for
    the chunked drivers (best-so-far cut; lockstep has no chunk loop to
    cut, so the combination raises). ``obs`` threads a per-chunk event
    emitter (``repro.obs.Tracer``) into the chunked drivers; lockstep
    ignores it (one unbounded program, nothing per-chunk to report). The
    host prep (solver routing, the admission check, then each driver's
    thresholds, lane padding and upload) is marked as ``solve.prepare``
    regions (``repro.obs.region``).

    ``policy.solver`` routes the bucket through the solver portfolio
    (push-relabel / Sinkhorn / hybrid / measured-auto); the chosen
    solver, the cost model's prediction, and the measured dispatch wall
    time are annotated onto the returned stats (``solver`` /
    ``predicted_s`` / ``solve_s``)."""
    policy = policy or DispatchPolicy()
    with region(obs, "solve.prepare"):
        solver, spec, predicted = _resolve_solver(spec, policy, inputs, eps)
    t0 = _now()
    if solver == "hybrid":
        from ..portfolio.hybrid import dispatch_hybrid

        r, stats = dispatch_hybrid(
            inputs, eps, sizes=sizes, policy=policy,
            keep_state=keep_state, deadline=deadline, obs=obs, **prep_kw)
    else:
        r, stats = _dispatch_one(
            spec, inputs, eps, sizes=sizes, policy=policy,
            keep_state=keep_state, deadline=deadline, obs=obs, **prep_kw)
    solve_s = _now() - t0
    if stats is not None:
        # driver stats are plain mutable dataclasses; a stats object
        # that refuses the annotation just goes without it
        for kk, v in (("solver", solver), ("predicted_s", predicted),
                      ("solve_s", solve_s)):
            try:
                setattr(stats, kk, v)
            except (AttributeError, TypeError):
                pass
    return r, stats


def _dispatch_one(
    spec,
    inputs: Dict[str, Any],
    eps,
    *,
    sizes=None,
    policy: Optional[DispatchPolicy] = None,
    keep_state: bool = False,
    deadline: Optional[float] = None,
    obs=None,
    **prep_kw,
):
    """The single-solver dispatch body: mode routing only (the solver
    was already resolved by :func:`dispatch`)."""
    policy = policy or DispatchPolicy()
    mode = policy.resolved_mode()
    if policy.fused:
        spec = fused_variant(spec)
    if policy.validate:
        from .validate import check_admission
        with region(obs, "solve.prepare"):
            check_admission(spec.canonicalize(inputs), sizes=sizes)
    if mode == "lockstep":
        if deadline is not None:
            raise ValueError(
                "deadline requires a chunked driver (mode='compact' or "
                "'mesh'); the lockstep path dispatches one unbounded "
                "program that cannot be cut mid-flight")
        eps_u = np.unique(np.asarray(eps, np.float64))
        if eps_u.size > 1:
            raise ValueError("per-instance eps requires compact=True")
        r, state = spec.solve_lockstep(
            inputs, float(eps_u[0]), sizes=sizes,
            guaranteed=policy.guaranteed, keep_state=keep_state, **prep_kw)
        if keep_state:
            b = int(np.shape(inputs["c"])[0])
            st = CompactionStats(batch=b, dispatched_batch=b, chunk=0,
                                 dispatches=1, final_state=state)
            return r, st
        return r, None
    k = DEFAULT_CHUNK if policy.chunk is None else int(policy.chunk)
    if mode == "compact":
        return solve_compacting(
            spec, inputs, eps, sizes=sizes, k=k,
            guaranteed=policy.guaranteed, keep_state=keep_state,
            deadline=deadline, obs=obs, **prep_kw)
    if mode == "mesh":
        return solve_mesh(
            spec, inputs, eps, policy.mesh, sizes=sizes, k=k,
            guaranteed=policy.guaranteed, placement=policy.placement,
            keep_state=keep_state, deadline=deadline, obs=obs, **prep_kw)
    raise ValueError(f"unknown dispatch mode {mode!r}")


def _wrap_solution(
    spec, inputs: Dict[str, Any], eps, policy: DispatchPolicy,
    r, stats, *, sizes, want: Optional[Tuple[str, ...]],
    bucket: Optional[Tuple[int, int]] = None,
    solver: str = "pushrelabel", predicted: Optional[float] = None,
) -> SolutionBatch:
    """Wrap one dispatched bucket result in a SolutionBatch (the typed
    surface); device arrays stay put until an artifact is fetched."""
    inputs_c = spec.canonicalize(inputs)
    b = int(spec.batch_shape(inputs_c)[0])
    eps_user = np.broadcast_to(np.asarray(eps, np.float64), (b,)).copy()
    eps_internal = eps_user / 3.0 if policy.guaranteed else eps_user
    sstats = SolveStats.from_driver(stats, mode=policy.resolved_mode(),
                                    batch=b, bucket=bucket, solver=solver,
                                    predicted_s=predicted)
    state = getattr(stats, "final_state", None) if stats is not None else None
    un = getattr(stats, "unconverged", None) if stats is not None else None
    degraded = None if un is None else np.asarray(un, bool)[:b]
    return SolutionBatch(
        spec, r, stats=sstats, driver_stats=stats, inputs=inputs_c,
        sizes=sizes, eps=eps_user, eps_internal=eps_internal,
        guaranteed=policy.guaranteed, want=want, state=state,
        degraded=degraded)


def solve(
    spec,
    instances: Union[Sequence, Dict[str, Any]],
    eps,
    policy: Optional[DispatchPolicy] = None,
    *,
    sizes=None,
    keep_state: bool = False,
    want: Optional[Sequence[str]] = None,
    deadline: Optional[float] = None,
    obs=None,
    **prep_kw,
) -> Union[SolutionBatch, List[Solution], Tuple[Any, Any], List[dict]]:
    """The front door. Two input forms:

    * ``instances`` is a DICT of pre-batched (B, ...) operands (``{"c":
      ...}`` for ``ASSIGNMENT``, ``{"c": ..., "nu": ..., "mu": ...}`` for
      ``OT``; ``sizes`` gives true shapes inside the padding): one bucket
      is dispatched — this is what the serving layers call per bucket.
      Returns a :class:`SolutionBatch` when ``want`` is declared, the
      legacy ``(result, stats)`` tuple otherwise.

    * ``instances`` is a ragged LIST (cost matrices for ``ASSIGNMENT``,
      ``(c, nu, mu)`` triples for ``OT``): instances are grouped into
      shape buckets (``policy.buckets``), padded, dispatched per bucket.
      Returns per-instance :class:`Solution` views (input order) when
      ``want`` is declared, the legacy per-instance dicts otherwise.
      ``eps`` may be per-instance; under lockstep mode each bucket is
      sub-grouped by eps value (lockstep bakes eps into the compiled
      program), so mixed-accuracy sets work under EVERY policy.

    ``want`` declares the artifacts the caller will fetch (see
    ``spec.artifacts``; e.g. ``("cost", "duals", "plan_sparse")``). The
    pre-completion integer ``state`` is just another artifact: asking for
    it (or passing ``keep_state=True``) retains it on every dispatch
    path, including lockstep and the ragged form.

    ``deadline`` (absolute ``time.monotonic()``) threads a wall-clock
    budget into the chunked drivers: dispatching stops when the next
    k-phase chunk would overrun it, and lanes cut before their
    termination predicate fired come back flagged
    ``Solution.degraded=True`` — still primal-feasible with eps-feasible
    duals, so ``dual_feasible()``/``additive_gap()`` re-validate the
    partial answer per request.

    ``obs`` threads an optional event emitter (``repro.obs.Tracer``) into
    the chunked drivers for per-chunk phase/occupancy/compile-cache
    events; results are bit-identical with or without it.
    """
    policy = policy or DispatchPolicy()
    if want is None:
        want = policy.want
    if want is not None:
        want = tuple(want)
        unknown = [w for w in want if w not in spec.artifacts]
        if unknown:
            raise ValueError(f"unknown artifact(s) {unknown} for spec "
                             f"{spec.name!r}; available: {spec.artifacts}")
        if keep_state and "state" not in want:
            # an explicit keep_state IS a request for the state artifact:
            # promote it into the declaration rather than retaining a
            # state the gating would then refuse to hand over
            want = want + ("state",)
        keep_state = keep_state or "state" in want
    if isinstance(instances, dict):
        if want is None:
            return dispatch(spec, instances, eps, sizes=sizes,
                            policy=policy, keep_state=keep_state,
                            deadline=deadline, obs=obs, **prep_kw)
        r, stats = dispatch(spec, instances, eps, sizes=sizes,
                            policy=policy, keep_state=keep_state,
                            deadline=deadline, obs=obs, **prep_kw)
        # re-resolve (deterministic) to wrap with the spec that actually
        # produced r: SINKHORN's result shape for sinkhorn routing, the
        # OT base for hybrid (its finish IS a push-relabel solve)
        solver, wspec, predicted = _resolve_solver(spec, policy,
                                                   instances, eps)
        return _wrap_solution(wspec, instances, eps, policy, r, stats,
                              sizes=sizes, want=want, solver=solver,
                              predicted=predicted)
    sols = _solve_ragged(spec, list(instances), eps, policy,
                         keep_state=keep_state, want=want,
                         deadline=deadline, obs=obs, **prep_kw)
    if want is not None:
        return sols
    # legacy adapter: the historical per-instance dicts, produced from the
    # same Solution views (bit-identical values; ``state`` rides along
    # when requested instead of raising as the pre-Solution surface did)
    out = []
    for s in sols:
        d = s.legacy_dict()
        if keep_state:
            d["state"] = s.state()
        out.append(d)
    return out


def _solve_ragged(spec, instances: list, eps, policy: DispatchPolicy,
                  *, keep_state: bool = False,
                  want: Optional[Tuple[str, ...]] = None,
                  deadline: Optional[float] = None,
                  obs=None,
                  **prep_kw) -> List[Solution]:
    from .batched import DEFAULT_BUCKETS, bucket_instances

    shapes = [spec.instance_shape(x) for x in instances]
    eps_arr = np.broadcast_to(np.asarray(eps, np.float64),
                              (len(instances),))
    buckets = (DEFAULT_BUCKETS if policy.buckets is None
               else tuple(policy.buckets))
    lockstep = policy.resolved_mode() == "lockstep"
    results: List[Optional[Solution]] = [None] * len(instances)
    for grp in bucket_instances(shapes, buckets):
        if lockstep:
            # lockstep compiles eps into the program: sub-group the
            # bucket by eps value so mixed-accuracy sets still dispatch
            by_eps: Dict[float, List[int]] = {}
            for i in grp.indices:
                by_eps.setdefault(float(eps_arr[i]), []).append(i)
            subgroups = [by_eps[e] for e in sorted(by_eps)]
        else:
            subgroups = [grp.indices]
        for idx in subgroups:
            inputs = spec.pad_group([instances[i] for i in idx], grp.key)
            sz = np.asarray([shapes[i] for i in idx], np.int32)
            r, stats = dispatch(spec, inputs, eps_arr[idx], sizes=sz,
                                policy=policy, keep_state=keep_state,
                                deadline=deadline, obs=obs, **prep_kw)
            # per-bucket re-resolution (auto may route buckets to
            # different solvers); deterministic, so it matches dispatch
            solver, wspec, predicted = _resolve_solver(
                spec, policy, inputs, eps_arr[idx])
            batch = _wrap_solution(wspec, inputs, eps_arr[idx], policy, r,
                                   stats, sizes=sz, want=want,
                                   bucket=grp.key, solver=solver,
                                   predicted=predicted)
            # per-instance views share the batch's device arrays and its
            # fetch cache: one device->host fetch per artifact per
            # bucket, never per instance
            for j, i in enumerate(idx):
                results[i] = batch[j]
    return results
