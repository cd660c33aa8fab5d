"""Quickstart: epsilon-approximate optimal transport in three calls.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax.numpy as jnp

from repro.core import build_cost_matrix, solve_assignment, solve_ot, sinkhorn
from repro.core.exact import exact_assignment_cost


def main():
    rng = np.random.default_rng(0)
    n = 256
    x = rng.uniform(size=(n, 2)).astype(np.float32)
    y = rng.uniform(size=(n, 2)).astype(np.float32)

    # 1. cost matrix (use kernel="pallas" on TPU)
    c = build_cost_matrix(jnp.asarray(x), jnp.asarray(y), "euclidean")

    # 2. assignment (paper Section 2): eps-approximate matching + duals
    r = solve_assignment(c, eps=0.05)
    opt = exact_assignment_cost(np.asarray(c))
    print(f"assignment: cost={float(r.cost):.4f} exact={opt:.4f} "
          f"phases={int(r.phases)} propose_rounds={int(r.rounds)}")
    print(f"  additive gap per point: "
          f"{(float(r.cost) - opt) / n:.5f}  (guarantee: 3*eps*max_c)")
    print(f"  dual certificate (lower bound): "
          f"{float(jnp.sum(r.y_b) + jnp.sum(r.y_a)):.4f}")

    # 3. general OT (paper Section 4): arbitrary masses, compact plan
    nu = rng.dirichlet(np.ones(n)).astype(np.float32)
    mu = rng.dirichlet(np.ones(n)).astype(np.float32)
    ot = solve_ot(c, jnp.asarray(nu), jnp.asarray(mu), eps=0.05)
    plan = np.asarray(ot.plan)
    print(f"OT: cost={float(ot.cost):.5f} phases={int(ot.phases)} "
          f"plan_nnz={(plan > 1e-12).sum()} (compact: <= 2n + n)")
    print(f"  marginal error: row={np.abs(plan.sum(1) - nu).max():.2e} "
          f"col={np.abs(plan.sum(0) - mu).max():.2e}")

    # 4. the baseline the paper compares against
    sk = sinkhorn(c, jnp.asarray(nu), jnp.asarray(mu), reg=0.01, tol=1e-6)
    print(f"sinkhorn: cost={float(sk.cost):.5f} iters={int(sk.iters)}")

    # 5. batched API: B instances as ONE XLA program. Ragged shapes are
    #    bucketed + padded (padding is masked, so each result equals its
    #    unbatched solve); one compiled program per bucket serves every
    #    future batch of that bucket - no per-shape recompiles.
    from repro.core import solve_ot_ragged

    insts = []
    for _ in range(6):
        m = int(rng.integers(40, 120))
        xb = rng.uniform(size=(m, 2)).astype(np.float32)
        yb = rng.uniform(size=(m, 2)).astype(np.float32)
        cb = build_cost_matrix(jnp.asarray(xb), jnp.asarray(yb), "euclidean")
        nub = rng.dirichlet(np.ones(m)).astype(np.float32)
        mub = rng.dirichlet(np.ones(m)).astype(np.float32)
        insts.append((np.asarray(cb), nub, mub))
    outs = solve_ot_ragged(insts, eps=0.05)   # compact=True by default
    for i, o in enumerate(outs):
        print(f"batched[{i}]: cost={o['cost']:.5f} bucket={o['bucket']} "
              f"batch_size={o['batch_size']} plan={o['plan'].shape} "
              f"dispatches={o['dispatches']}")

    # 6. convergence compaction: each bucket above was actually solved as a
    #    sequence of k-phase dispatches; converged instances retire between
    #    dispatches instead of running lockstep until the bucket's slowest
    #    instance finishes. The driver is available directly - it returns
    #    occupancy/waste stats, and eps may be per-instance (mixed-accuracy
    #    batches, inexpressible in the lockstep path):
    from repro.core import solve_ot_batched_compacting
    from repro.core.batched import pad_stack

    b, nmax = len(insts), max(c.shape[0] for c, _, _ in insts)
    cb = pad_stack([ci for ci, _, _ in insts], (nmax, nmax))
    nub = pad_stack([nui for _, nui, _ in insts], (nmax,))
    mub = pad_stack([mui for _, _, mui in insts], (nmax,))
    sizes = np.asarray([ci.shape for ci, _, _ in insts], np.int32)
    eps_each = np.where(np.arange(b) % 2 == 0, 0.05, 0.1)  # per-instance!
    res, stats = solve_ot_batched_compacting(cb, nub, mub, eps_each,
                                             sizes=sizes, k=4)
    print(f"compaction: dispatches={stats.dispatches} "
          f"occupancy={stats.occupancy} "
          f"phases_needed={stats.phases_needed} vs "
          f"lockstep_slot_phases={stats.lockstep_slot_phases}")

    # 7. resumable stepped core underneath it all: a solve is just
    #    init_state -> run_phases(k) until converged, bit-identical to the
    #    one-shot solver for every chunk size k (see core/pushrelabel.py
    #    and core/transport.py for the assignment/OT stepped APIs).

    # 8. distributed dispatch: the same compacting driver with the BATCH
    #    axis sharded across a device mesh (core/distributed.py). On a
    #    multi-device host (or under XLA_FLAGS=--xla_force_host_platform_
    #    device_count=8) each k-phase dispatch runs shard_map'ed over the
    #    mesh and re-bucketing re-shards the survivors; on this host it
    #    degrades gracefully to the single-device driver. Results are
    #    bit-identical either way. A placement policy routes a few LARGE
    #    instances to row/col matrix sharding (core/sharded.py) instead.
    from repro.core import solve_ot_distributed
    from repro.launch.mesh import make_batch_mesh

    mesh = make_batch_mesh()   # 1-D pow2 batch mesh over the host devices
    res_d, dstats = solve_ot_distributed(cb, nub, mub, eps_each,
                                         sizes=sizes, k=4, mesh=mesh)
    assert np.array_equal(np.asarray(res_d.plan), np.asarray(res.plan))
    print(f"distributed: devices={dstats.devices} "
          f"placement={dstats.placement} dispatches={dstats.dispatches} "
          f"(bit-identical to the single-device compacting solve)")

    # 9. async multi-tenant serving front end (serve/scheduler.py): submit
    #    from any thread -> Future; a collate worker buckets/pads/builds
    #    cost matrices for the NEXT batch while the dispatch worker's
    #    current batch is in flight on the mesh; per-request stats report
    #    queue wait, solve time, phase counts, and the occupancy curve.
    from repro.serve.scheduler import AsyncOTScheduler

    with AsyncOTScheduler(eps=0.05, mesh=mesh, linger_ms=5) as sched:
        futs = []
        for i in range(4):
            m = int(rng.integers(30, 80))
            xs = rng.uniform(size=(m, 2)).astype(np.float32)
            ys = rng.uniform(size=(m, 2)).astype(np.float32)
            # per-request eps: mixed-accuracy tenants share dispatches
            futs.append(sched.submit(xs, ys, eps=0.05 if i % 2 else 0.1))
        sched.flush()
        for i, f in enumerate(futs):
            r = f.result()
            print(f"scheduler[{i}]: cost={r['cost']:.4f} "
                  f"eps={r['eps']} wait={r['wait_s'] * 1e3:.1f}ms "
                  f"batch={r['batch_size']} devices={r['devices']}")

    # 10. the unified solve() front door (core/api.py). Everything above —
    #     lockstep batches, compaction, mesh dispatch, the serving layers —
    #     routes through ONE entry point: a ProblemSpec (core/problem.py)
    #     captures the paper's stepped-core contract (prepare -> prologue
    #     -> init_state -> run_phases(k) -> converged -> epilogue, i.e.
    #     Algorithm 1/2), and a DispatchPolicy picks the driver. The same
    #     call solves a ragged list under any policy, with identical
    #     results:
    from repro.core import ASSIGNMENT, OT, DispatchPolicy, solve

    ragged = [c for c, _, _ in insts]
    for mode in ("lockstep", "compact", "mesh"):
        pol = DispatchPolicy(mode=mode,
                             mesh=mesh if mode == "mesh" else None)
        outs10 = solve(ASSIGNMENT, ragged, eps_each, pol)
        print(f"solve(ASSIGNMENT, policy={mode}): "
              f"costs={[round(o['cost'], 4) for o in outs10[:3]]}...")
    # pre-batched buckets dispatch through the same door (this is what
    # OTService / AsyncOTScheduler call per bucket):
    r10, st10 = solve(OT, {"c": cb, "nu": nub, "mu": mub}, eps_each,
                      DispatchPolicy(mode="compact", chunk=4), sizes=sizes)
    assert np.array_equal(np.asarray(r10.plan), np.asarray(res.plan))
    print(f"solve(OT, bucket): dispatches={st10.dispatches} "
          f"(identical to section 6's driver call)")

    # 11. the typed Solution surface (core/solution.py): declare the
    #     artifacts you will read with want=, and only those ever cross
    #     device->host. A cost-only request fetches O(B) scalars instead
    #     of the O(B*n^2) dense plans (the byte win is the point: on an
    #     accelerator that fetch is interconnect traffic); the plan ships
    #     as compact COO triplets (the paper's sparse-support claim) that
    #     reconstruct the dense plan bit for bit; and the approximate
    #     DUAL solution yields an a-posteriori certificate: additive_gap()
    #     <= eps * m * max(c) under guaranteed=True (paper Thm 1.2/1.3).
    cost_only = solve(OT, {"c": cb, "nu": nub, "mu": mub}, eps_each,
                      DispatchPolicy(mode="compact", chunk=4), sizes=sizes,
                      want=("cost",))
    dense_bytes = int(np.prod(cb.shape)) * 4
    print(f"solve(want=('cost',)): costs={np.round(cost_only.cost(), 4)} "
          f"fetched {cost_only.fetched_bytes}B (dense plans would move "
          f"{dense_bytes}B — {dense_bytes // cost_only.fetched_bytes}x)")
    sols = solve(OT, insts, 0.05,
                 DispatchPolicy(mode="compact", guaranteed=True),
                 want=("cost", "duals", "plan_sparse"))
    s0 = sols[0]
    sp = s0.plan_sparse()
    assert np.array_equal(
        sp.to_dense(),
        solve(OT, insts, 0.05,
              DispatchPolicy(mode="compact", guaranteed=True),
              want=("plan",))[0].plan())
    print(f"Solution[0]: cost={s0.cost:.5f} plan_nnz={sp.nnz} "
          f"({sp.nbytes}B sparse vs {4 * sp.shape[0] * sp.shape[1]}B "
          f"dense, to_dense() bit-identical)")
    print(f"  certificate: additive_gap={s0.additive_gap():.5f} <= "
          f"eps*m*max(c)={s0.additive_gap_bound():.5f} "
          f"dual_feasible={s0.dual_feasible()} "
          f"(stats: {s0.stats.mode}, {s0.stats.dispatches} dispatches on "
          f"{s0.stats.devices} device(s))")

    # 12. auditing your own ProblemSpec (repro.analysis): every jitted
    #     entry point used above — the stepped cores, the compaction and
    #     mesh chunk dispatches, the kernel wrappers, the certificate
    #     reductions — self-registers with repro.analysis and is traced
    #     to a jaxpr, then audited for the bug classes this repo has
    #     actually shipped: donated-buffer aliasing, f32 threshold drift,
    #     baked-operand recompiles, hot-loop host syncs.
    #     `python -m repro.analysis --strict` is the CI gate. A custom
    #     spec's chunk dispatch is audited the same way — trace it with
    #     its donation contract and run the rules:
    from repro.analysis import registry, rules

    def my_init_chain(cost, demand):
        # BUG (on purpose): same-dtype astype is elided by jax, so the
        # state's supply vector ALIASES the retained demand buffer — the
        # chunk dispatch donates the state, freeing the buffer the
        # epilogue still reads. This is the bug class
        # rule_donation_safety exists to catch (fix: jnp.array(...,
        # copy=True), as in init_ot_state).
        d_int = jnp.ceil(demand * 32.0).astype(jnp.int32)
        state = {"free": d_int.astype(jnp.int32),
                 "y": jnp.zeros_like(d_int)}
        return {"state": state, "retained": {"d_int": d_int}}

    ent = registry.trace_entry(
        "quickstart.my_init_chain", my_init_chain,
        {"cost": jnp.zeros((8, 8), jnp.float32),
         "demand": jnp.full((8,), 0.125, jnp.float32)},
        retained={"cost", "demand"}, tags={"state-init-chain"})
    flagged = rules.audit_entry(ent)
    print(f"analysis: my_init_chain -> {len(flagged)} finding(s) "
          f"{[f.key for f in flagged]}")
    assert any(f.rule == "donation-safety" for f in flagged)
    repo_findings, n_entries = rules.audit_entries(registry.build_entries())
    print(f"analysis: repo audit traced {n_entries} entries, "
          f"{len(repo_findings)} finding(s) (each carries a justification "
          f"in repro/analysis/baseline_suppressions.txt; debug-mode "
          f"sanitizers: REPRO_DEBUG_CHECKS=1)")

    # 13. fault-tolerant serving: deadlines, degraded answers you can
    #     re-validate, and poisoned-instance quarantine.
    #     solve(..., deadline=) gives the chunked drivers an absolute
    #     wall-clock budget: the chunk loop stops dispatching when the
    #     budget is at risk and returns best-so-far Solutions flagged
    #     degraded=True. The duals stay eps-feasible at EVERY phase
    #     (invariant I2), so a degraded answer still carries a valid
    #     a-posteriori certificate — its additive_gap() is honestly
    #     larger, not wrong.
    import time as _time

    budget = solve(OT, insts, 0.05, DispatchPolicy(mode="compact", chunk=1),
                   want=("cost", "duals"), deadline=_time.monotonic())
    d0 = budget[0]
    print(f"deadline: degraded={d0.degraded} "
          f"dual_feasible={d0.dual_feasible()} "
          f"gap={float(d0.additive_gap()):.4f} "
          f"(vs converged {float(s0.additive_gap()):.4f})")
    assert d0.degraded and bool(d0.dual_feasible())

    #     Poisoned inputs never take down a batch: the serving layers
    #     (OTService / AsyncOTScheduler) run a vectorized admission gate
    #     per collated bucket — a NaN-poisoned request is rejected with
    #     RequestRejected while its healthy neighbors solve, bit-identical
    #     to a clean run. Dispatch-time poison (with validation off and
    #     REPRO_DEBUG_CHECKS=1, the checkify sanitizer trips mid-solve)
    #     is isolated by bisection; transient dispatch failures retry
    #     down a mesh -> compact -> host-CPU degradation ladder. The
    #     chaos harness (serve/faults.py) injects all of it
    #     deterministically:
    from repro.serve.faults import FaultInjector, FaultPlan
    from repro.serve.ft import RequestRejected
    from repro.serve.scheduler import AsyncOTScheduler

    inj = FaultInjector(FaultPlan(poison_submits=(1,)))
    pts = [np.random.default_rng(s).standard_normal((12, 2)).astype(
        np.float32) for s in range(8)]
    with AsyncOTScheduler(eps=0.1, linger_ms=50, faults=inj) as sched:
        futs = [sched.submit(pts[2 * i], pts[2 * i + 1],
                             tenant=f"tenant-{i}") for i in range(4)]
        outcomes = []
        for f in futs:
            try:
                outcomes.append(f"{f.result(timeout=300)['cost']:.4f}")
            except RequestRejected as e:
                outcomes.append(f"rejected({e.reason})")
        sd = sched.stats_dict()
    print(f"chaos: {outcomes} "
          f"(rejected={sd['rejected']} quarantined={sd['quarantined']} "
          f"retries={sd['retries']})")
    assert sum(o.startswith("rejected") for o in outcomes) == 1

    # 14. live observability (repro.obs): attach a sink and the whole
    #     request path streams out as structured events — per-request
    #     root spans (submit -> resolve), per-bucket collate/admission/
    #     dispatch/solve/artifact-fetch spans, the chunked drivers'
    #     per-chunk events, and the fault events from section 13
    #     (rejected/retry/ladder/quarantine/deadline-cut/degraded).
    #     stats_dict() is a VIEW over the same registry the sink streams
    #     from, so the numbers can never disagree; with no sink attached
    #     the whole layer costs <2% (benchmarks/bench_serve.py asserts
    #     the budget). JSONLSink writes one JSON object per line —
    #     here we demo the in-memory sink and render a span tree.
    import json as _json
    import tempfile as _tempfile

    from repro.obs import InMemorySink, JSONLSink, span_tree

    mem = InMemorySink()
    with _tempfile.TemporaryDirectory() as tmp:
        jpath = f"{tmp}/serve.jsonl"
        jsink = JSONLSink(jpath)
        with AsyncOTScheduler(eps=0.1, linger_ms=50,
                              sinks=(mem, jsink)) as sched:
            fut = sched.submit(pts[0], pts[1], tenant="healthy")
            fut.result(timeout=300)
            sched.flush()
        jsink.close()
        rows = [_json.loads(ln) for ln in open(jpath)]
    print(f"obs: JSONL sink wrote {len(rows)} rows "
          f"({sum(r['kind'] == 'event' for r in rows)} events, "
          f"{sum(r['kind'] == 'counter' for r in rows)} counter "
          f"increments)")
    (root,) = mem.spans("request")
    print("obs: healthy request span tree (one monotonic clock):")
    for ln in span_tree(mem.spans(), "req-0").splitlines():
        print(f"  {ln}")
    for ln in span_tree(mem.spans(), root["bucket_trace"]).splitlines():
        print(f"  {ln}")
    chunk = mem.events("chunk")
    print(f"obs: {len(chunk)} driver chunk event(s), e.g. live={{"
          f"{', '.join(str(e['live']) for e in chunk)}}} "
          f"compiled_delta={chunk[0]['compiled']}")

    #     the same stream captures faults: re-run section 13's poisoned
    #     tenant with a sink attached and the rejection (plus any
    #     retries/ladder drops) appears as events alongside the spans.
    mem2 = InMemorySink()
    inj2 = FaultInjector(FaultPlan(poison_submits=(0,),
                                   transient_dispatches=1))
    with AsyncOTScheduler(eps=0.1, linger_ms=50, faults=inj2,
                          sinks=(mem2,)) as sched:
        bad = sched.submit(pts[0], pts[1], tenant="poisoned")
        ok = sched.submit(pts[2], pts[3], tenant="healthy")
        try:
            bad.result(timeout=300)
        except RequestRejected:
            pass
        ok.result(timeout=300)
        sched.flush()
    rej = mem2.events("rejected")
    ret = mem2.events("retry")
    outcomes14 = sorted(s["outcome"] for s in mem2.spans("request"))
    print(f"obs: fault run streamed {len(rej)} rejected event(s), "
          f"{sum(e['n'] for e in ret)} retry(ies); "
          f"request outcomes={outcomes14}")
    assert outcomes14 == ["rejected", "resolved"]

    # 15. the fused phase kernel: DispatchPolicy(fused=True) swaps the
    #     k-phase inner loop for ONE Pallas kernel per chunk — slack +
    #     propose/accept + push + relabel with the solver state resident
    #     in VMEM across all k phases, instead of round-tripping through
    #     XLA/HBM between the slack_propose kernel and the state
    #     updates. Results are BIT-IDENTICAL to the stepped cores
    #     (tests/test_fused_phase.py asserts it across k, padded lanes,
    #     mixed per-instance eps, and every dispatch mode), so it is a
    #     pure perf knob. Block sizes resolve per backend from the table
    #     in kernels/ops.py (kernel_blocks); off-TPU the kernel runs in
    #     interpret mode — the committed BENCH_kernels.json rows carry
    #     mode=interpret|compiled so CPU numbers are never mistaken for
    #     accelerator numbers.
    from repro.kernels.ops import kernel_blocks

    pol_fused = DispatchPolicy(mode="compact", chunk=4, fused=True)
    r_f, _ = solve(OT, {"c": cb, "nu": nub, "mu": mub}, 0.1, pol_fused)
    r_s, _ = solve(OT, {"c": cb, "nu": nub, "mu": mub}, 0.1,
                   DispatchPolicy(mode="compact", chunk=4))
    assert np.array_equal(np.asarray(r_f.plan), np.asarray(r_s.plan))
    print(f"fused: compact dispatch through the fused kernel matches the "
          f"stepped core exactly (cost {float(r_f.cost[0]):.4f}); "
          f"fused_phase blocks for this backend = "
          f"{kernel_blocks('fused_phase')}")

    #     benchmarks/bench_kernels.py writes BENCH_kernels.json
    #     (us/phase + phases/sec per kernel, fused vs stepped, parity-
    #     asserted per row; gated by benchmarks/run.py --diff in CI):
    #
    #         {"name": "kernels/assignment_phase/fused/n=256/...",
    #          "us_per_call": ..., "instances_per_s": ...,
    #          "mode": "interpret"}
    #
    #     for GPU launches, launch/platform.py pins the backend and
    #     installs the latency-hiding/async-stream XLA flags BEFORE the
    #     first jax computation (after backend init they are ignored):
    #
    #         from repro.launch.platform import set_platform
    #         set_platform("gpu")   # jax_platform_name + XLA_FLAGS

    # 16. the solver portfolio: DispatchPolicy(solver=...) picks HOW an
    #     OT batch is solved without changing what comes back — every
    #     solver certifies the same additive-eps target through the same
    #     Solution surface (additive_gap() <= additive_gap_bound(),
    #     dual_feasible()).
    #       "pushrelabel"  the paper's solver (default, exact phases)
    #       "sinkhorn"     log-domain entropic solver at the AWR schedule
    #                      (reg = eps/(4 ln n), marginal tol = eps/8),
    #                      rounded onto the transport polytope with
    #                      feasible duals in the epilogue
    #       "hybrid"       coarse Sinkhorn first, its duals rounded into
    #                      a feasible push-relabel start (all paper
    #                      invariants hold), push-relabel finishes — so
    #                      the guarantee is push-relabel's own
    #       "auto"         the measured cost model picks per batch
    from repro.portfolio import get_model

    batch16 = {"c": cb, "nu": nub, "mu": mub}
    for solver in ("pushrelabel", "sinkhorn", "hybrid"):
        pol16 = DispatchPolicy(mode="compact", solver=solver,
                               guaranteed=True)
        sols = solve(OT, batch16, 0.1, pol16,
                     want=("cost", "duals", "stats"))
        s0 = sols[0]
        assert bool(s0.dual_feasible())
        assert float(s0.additive_gap()) <= float(s0.additive_gap_bound())
        print(f"portfolio[{solver}]: cost={float(s0.cost):.4f} "
              f"gap={float(s0.additive_gap()):.5f} "
              f"<= bound={float(s0.additive_gap_bound()):.5f} "
              f"(certified, solve {sols.stats.actual_s * 1e3:.0f} ms)")

    #     solver="auto" consults the measured cost model committed at
    #     src/repro/portfolio/costmodel_default.json (per-instance
    #     seconds per (solver, n-bucket, eps-band), honest mode=
    #     interpret labels off-TPU). Refit it for YOUR hardware with
    #         PYTHONPATH=src python -m benchmarks.bench_portfolio \
    #             --calibrate --json mymodel.json
    #     then repro.portfolio.set_model(CostModel.load("mymodel.json")).
    #     The chosen solver and predicted-vs-actual seconds land in
    #     stats.
    pol_auto = DispatchPolicy(mode="compact", solver="auto")
    sols_a = solve(OT, batch16, 0.1, pol_auto, want=("cost", "stats"))
    model = get_model()
    print(f"portfolio[auto]: model={'loaded' if model else 'none'} "
          f"chose {sols_a.stats.solver!r} "
          f"(predicted {sols_a.stats.predicted_s} s, "
          f"actual {sols_a.stats.actual_s:.3f} s)")


if __name__ == "__main__":
    main()
