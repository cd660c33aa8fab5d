"""The paper's OT solver as a synchronous batched endpoint: ``OTService``
queues point-set (assignment) and mass (general OT) requests, buckets them
by shape and solves each bucket through ``core/api.solve``, with cost
matrices built by the Pallas kernel on TPU. ``OTRequest`` is one queued
request."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from repro.obs import MetricsRegistry, Tracer, new_id
from repro.obs import now as _now


@dataclass
class OTRequest:
    x: np.ndarray                      # (m, d) supply points
    y: np.ndarray                      # (n, d) demand points
    nu: Optional[np.ndarray] = None    # (m,) masses -> general-OT mode
    mu: Optional[np.ndarray] = None    # (n,) masses


class OTService:
    """Batched OT-distance endpoint (the paper's solver as a service).

    ``submit()`` queues distance requests; ``run_batch()`` groups them into
    shape buckets, pads each bucket to a fixed shape, and dispatches every
    bucket through the unified ``core/api.solve`` front
    door (one DispatchPolicy per service: lockstep / compacting / mesh-
    distributed, chosen by the constructor arguments below). With
    ``compact=True`` (default) a bucket is solved by the convergence-
    compacting driver (core/compaction.py): converged requests retire
    between k-phase dispatches instead of riding lockstep until the bucket's
    slowest request finishes (a win on skewed traffic; pass compact=False
    for tiny/uniform workloads where the per-chunk converged-mask sync
    outweighs it). Point-set requests (no masses) run the
    assignment solver; requests with (nu, mu) run the general OT solver.
    ``distance()`` stays as the one-shot convenience wrapper.

    ``want=`` (a tuple of artifact names, e.g. ``("cost", "plan_sparse")``)
    switches ``run_batch`` onto the typed Solution surface
    (core/solution.py): it returns per-request
    :class:`~repro.core.solution.Solution` views instead of dicts, and
    only the declared artifacts ever cross device->host — cost-only
    services fetch O(B) scalars per bucket, never the dense plans. With
    ``want=None`` (default) run_batch is a thin adapter emitting the
    historical per-request dicts, bit-identical to the pre-Solution
    surface (including the legacy ``dispatches``/``devices`` keys, kept
    for one release — prefer ``Solution.stats``).

    Observability: the service owns a :class:`repro.obs.MetricsRegistry`
    (attach sinks via ``sinks=``). Every ``run_batch`` bucket gets its
    own trace (``svc-N``) with bucket/admission/solve/artifact-fetch
    spans, per-rejected-ticket events, and the chunked drivers' per-chunk
    events parented under the solve span. ``stats_dict()`` is a view
    over that registry — there is no hand-maintained tally, and results
    are bit-identical with or without a sink attached.
    """

    def __init__(self, eps: float = 0.05, metric: str = "euclidean",
                 use_pallas: bool = True, buckets=None,
                 compact: bool = True, chunk: Optional[int] = None,
                 mesh=None, want: Optional[tuple] = None,
                 validate: bool = True,
                 admission_tol: Optional[float] = None,
                 sinks=(), solver: str = "pushrelabel"):
        from repro.core import batched as B
        from repro.core import compaction as C
        from repro.core import validate as V
        from repro.core.api import DispatchPolicy
        from repro.core.costs import COSTS, build_cost_matrix

        self.eps = eps
        self.metric = metric
        # per-ticket admission gate: poisoned tickets get a
        # RequestRejected INSTANCE in the result list, healthy co-bucketed
        # tickets still solve (lane-independence of the batched drivers)
        self.validate = bool(validate)
        self.admission_tol = (V.DEFAULT_TOL if admission_tol is None
                              else float(admission_tol))
        # Pallas cost kernels only where they compile (TPU); everywhere else
        # they would run in interpret mode, i.e. a pure emulation tax.
        self.kernel = ("pallas" if use_pallas
                       and jax.default_backend() == "tpu" else "jnp")
        self.buckets = tuple(buckets) if buckets else B.DEFAULT_BUCKETS
        self.compact = compact
        self.chunk = C.DEFAULT_CHUNK if chunk is None else int(chunk)
        # mesh != None routes every bucket through the mesh-distributed
        # compacting driver (core/distributed.py): batch axis sharded
        # across devices, same per-request results. Every bucket solve
        # goes through the unified core/api.solve front door under this
        # one policy (from_legacy owns the compact/mesh keyword mapping
        # and its mesh-requires-compact rule).
        # ``solver`` routes OT-mode buckets through the solver portfolio
        # (core/api DispatchPolicy.solver: pushrelabel / sinkhorn /
        # hybrid / measured-"auto"); assignment-mode requests ignore it.
        self._policy = DispatchPolicy.from_legacy(
            compact, mesh, chunk=self.chunk, buckets=self.buckets,
            solver=solver)
        self.want = None if want is None else tuple(want)
        self.mesh = mesh
        self.queue: List[OTRequest] = []
        self._B = B
        self._C = C
        self._cost = build_cost_matrix
        self._cost_batched = jax.jit(jax.vmap(COSTS[metric]))
        # stats_dict() is a view over this registry; attach sinks to
        # stream the same observations out as structured events
        self.metrics = MetricsRegistry(sinks=sinks)
        self._tracer = Tracer(self.metrics)
        reg = self.metrics
        self._c_requests = reg.counter("service.requests")
        self._c_batches = reg.counter("service.batches")
        self._c_rejected = reg.counter("service.rejected")
        self._c_dispatches = reg.counter("service.dispatches")
        self._h_solve = reg.histogram("service.solve_s",
                                      MetricsRegistry.LATENCY_BOUNDS)

    def stats_dict(self) -> Dict[str, Any]:
        """Service counters as a plain dict — a view over the metrics
        registry (the same numbers any attached sink streamed out)."""
        snap = self.metrics.snapshot()
        solve_h = snap.get("service.solve_s", {"count": 0, "sum": 0.0})
        return {
            "requests": snap.get("service.requests", 0),
            "batches": snap.get("service.batches", 0),
            "rejected": snap.get("service.rejected", 0),
            "dispatches": snap.get("service.dispatches", 0),
            "total_solve_s": solve_h["sum"],
        }

    def submit(self, x: np.ndarray, y: np.ndarray,
               nu: Optional[np.ndarray] = None,
               mu: Optional[np.ndarray] = None) -> int:
        """Queue one distance request; returns its ticket (position in the
        result list of the next run_batch)."""
        from .ft import require_mass_pair

        require_mass_pair(nu, mu, who=f"ticket #{len(self.queue)}")
        self.queue.append(OTRequest(x=np.asarray(x), y=np.asarray(y),
                                    nu=nu, mu=mu))
        return len(self.queue) - 1

    def _batched_cost(self, xs, ys):
        if self.kernel == "pallas":
            from repro.kernels import ops

            # one kernel launch for the whole bucket: grid (B, m/BM, n/BN),
            # each batch slice bit-identical to the per-instance kernel
            return ops.cost_matrix_batched(xs, ys, metric=self.metric)
        return self._cost_batched(xs, ys)

    def run_batch(self) -> List[Any]:
        """Solve all queued requests via bucketed batched dispatch; returns
        results in submission order: the historical per-request dicts
        (``want=None``, bit-identical adapter), or per-request
        ``Solution`` views when the service declared ``want=``.

        With ``validate=True`` (default) each bucket passes the admission
        gate first: a poisoned ticket's slot holds its
        :class:`~repro.core.validate.RequestRejected` instance (not a
        result dict) while healthy co-bucketed tickets solve normally."""
        if not self.queue:
            return []
        from repro.core.api import ASSIGNMENT, OT, solve

        reqs, self.queue = self.queue, []
        results: List[Optional[Any]] = [None] * len(reqs)
        # Split by point dim + solver mode, then reuse the core bucketing
        # for the (m, n) shape grouping -- one compiled program per
        # (bucket, d, mode), shared by later batches of the same key.
        modes: Dict[tuple, List[int]] = {}
        for i, r in enumerate(reqs):
            modes.setdefault((r.x.shape[1], r.nu is not None), []).append(i)
        for (d, has_mass), sub in sorted(modes.items()):
            shapes = [(reqs[i].x.shape[0], reqs[i].y.shape[0]) for i in sub]
            for grp in self._B.bucket_instances(shapes, self.buckets):
                idx = [sub[j] for j in grp.indices]
                (mb, nb), sizes = grp.key, grp.sizes
                tid = new_id("svc")
                bsp = self._tracer.start("bucket", trace_id=tid,
                                         bucket=[int(mb), int(nb)],
                                         batch=len(idx),
                                         tickets=[int(i) for i in idx])
                gt0 = bsp.t_start
                xs = self._B.pad_stack([reqs[i].x for i in idx], (mb, d))
                ys = self._B.pad_stack([reqs[i].y for i in idx], (nb, d))
                c = self._batched_cost(xs, ys)
                nu = mu = None
                if has_mass:
                    nu = self._B.pad_stack([reqs[i].nu for i in idx], (mb,))
                    mu = self._B.pad_stack([reqs[i].mu for i in idx], (nb,))
                if self.validate:
                    from repro.core.validate import (RequestRejected,
                                                     admission_codes)

                    ins = ({"c": c, "nu": nu, "mu": mu} if has_mass
                           else {"c": c})
                    with self._tracer.span(
                            "admission", trace_id=tid,
                            parent=bsp.span_id) as asp:
                        codes = admission_codes(ins, sizes=sizes,
                                                tol=self.admission_tol)
                        bad = np.flatnonzero(codes != 0)
                        asp.attrs["rejected"] = int(bad.size)
                    if bad.size:
                        # quarantined tickets get their rejection IN the
                        # result list (run_batch has no Future to fail);
                        # the healthy rest of the bucket still solves
                        self._c_rejected.add(int(bad.size))
                        for j in bad:
                            self._tracer.event(
                                "rejected", trace_id=tid,
                                parent_id=bsp.span_id,
                                ticket=int(idx[j]), code=int(codes[j]))
                            results[idx[j]] = RequestRejected(
                                f"ticket #{idx[j]}", int(codes[j]))
                        keep = np.flatnonzero(codes == 0)
                        if keep.size == 0:
                            bsp.end(outcome="all-rejected")
                            continue
                        c = c[keep]
                        if has_mass:
                            nu, mu = nu[keep], mu[keep]
                        sizes = sizes[keep]
                        idx = [idx[j] for j in keep]
                if has_mass:
                    spec, inputs = OT, {"c": c, "nu": nu, "mu": mu}
                    legacy_want = ("cost", "plan")
                else:
                    spec, inputs = ASSIGNMENT, {"c": c}
                    legacy_want = ("cost", "matching", "duals")
                want = legacy_want if self.want is None else self.want
                with self._tracer.span("solve", trace_id=tid,
                                       parent=bsp.span_id,
                                       batch=len(idx)) as ssp:
                    batch = solve(spec, inputs, self.eps, self._policy,
                                  sizes=sizes, want=want,
                                  obs=self._tracer.bind(
                                      trace_id=tid, parent=ssp.span_id))
                with self._tracer.span("artifact-fetch", trace_id=tid,
                                       parent=bsp.span_id):
                    # the O(B)-scalar (ungated) phase fetch blocks until
                    # the bucket is solved regardless of the declared
                    # want; big artifacts stay on device unless requested
                    batch.phases()
                    if self.want is None:
                        # legacy latency_s includes the legacy artifact
                        # device->host fetches, as the pre-Solution
                        # surface measured it
                        batch.cost()
                        if has_mass:
                            batch.plan()
                        else:
                            batch.matching()
                            batch.duals()
                gdt = _now() - gt0
                st = batch.driver_stats
                self._c_batches.add(1)
                self._c_requests.add(len(idx))
                self._h_solve.observe(gdt)
                if st is not None:
                    self._c_dispatches.add(int(st.dispatches))
                bsp.end(kept=len(idx), solve_s=gdt)
                for k, i in enumerate(idx):
                    sol = batch[k]
                    if self.want is not None:
                        results[i] = sol
                        continue
                    m, n = sizes[k]
                    if has_mass:
                        out: Dict[str, Any] = {
                            "cost": sol.cost,
                            "plan": sol.plan(),
                            "phases": sol.phases,
                            "batch_size": len(idx),
                            "bucket": (mb, nb),
                            "latency_s": gdt,
                        }
                    else:
                        y_b, y_a = sol.duals()
                        out = {
                            "cost": sol.cost / m,
                            "matching": sol.matching(),
                            "phases": sol.phases,
                            "dual_lower_bound": float(
                                (y_b.sum() + y_a.sum()) / m
                            ),
                            "batch_size": len(idx),
                            "bucket": (mb, nb),
                            "latency_s": gdt,
                        }
                    # legacy keys, kept for one release: uniform
                    # accounting now lives on Solution.stats
                    if st is not None:
                        out["dispatches"] = st.dispatches
                        if hasattr(st, "devices"):
                            out["devices"] = st.devices
                    results[i] = out
        assert all(r is not None for r in results)
        return results  # submission order

    def distance(self, x: np.ndarray, y: np.ndarray,
                 nu: Optional[np.ndarray] = None,
                 mu: Optional[np.ndarray] = None) -> Dict[str, Any]:
        """One-shot convenience: solve just this request. Queued requests
        and their tickets are left untouched for the next run_batch()."""
        held, self.queue = self.queue, []
        try:
            self.submit(x, y, nu=nu, mu=mu)
            out = self.run_batch()[0]
            if isinstance(out, BaseException):
                raise out        # one-shot callers want the exception
            return out
        finally:
            self.queue = held
