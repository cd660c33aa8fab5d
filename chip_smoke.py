#!/usr/bin/env python3
"""Smoke run of the certified OT path on a TPU.

    python3 chip_smoke.py [--seed S]          # one chip: every phase below
    python3 chip_smoke.py --chips 4 [--seed S]  # the four-chip placements only

One process, no children. Each phase drives the system through the entry
points a user calls (``repro.core.solve``, ``AsyncOTScheduler``, the Pallas
kernel wrappers) and checks what comes out by the repo's own means: the
a-posteriori certificate of every solution (``dual_feasible()`` and
``additive_gap() <= additive_gap_bound()``, the paper's eps * m * max(c)
bound under ``guaranteed=True``), the exact solvers of ``core/exact.py`` at
n = 256, and the pure-jnp oracles or stepped cores for the kernels.

Phases on one chip:
  gate        fail unless JAX's backend is a TPU and Pallas kernels compile
              (no interpret mode); print the device and the compile cache
  assignment  n = 10 000 uniform 2-D points, Euclidean cost / max, eps 0.1
              (arXiv:2203.03732 section 5), default compact stepped policy
  ot          DOTmark WhiteNoise pair on the 128 x 128 grid (n = 16384),
              squared-Euclidean cost, eps 0.1
  reference   assignment and OT at n = 256 against core/exact.py
  serving     32 requests through AsyncOTScheduler (half OT, half
              assignment, heavy-tailed n in [64, 2048], eps in {0.1, 0.05});
              any retry, degraded answer or fallback rung fails
  kernels     every pallas_call entry point, compiled, at n = 1024

``--chips 4`` runs only the four-chip phase: a uniform and a ragged OT batch
on the four-device batch mesh (bit-identical to single-device compaction;
the uniform one must spread its bytes over the four devices) and one
n = 16384 OT instance under matrix placement (integer state exact, floats
to 1e-6 against the single-device solve), with each device's peak bytes.

Any failure exits non-zero. On success the last line of stdout is one JSON
object, ``{"ok": true, "device": {...}}``; every other line comes before it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import exact  # noqa: E402
from repro.core.api import ASSIGNMENT, OT, DispatchPolicy, solve  # noqa: E402
from repro.core.costs import euclidean, sqeuclidean  # noqa: E402
from repro.launch.mesh import make_batch_mesh  # noqa: E402
from repro.launch.platform import use_compile_cache  # noqa: E402

# every solve is certified against the paper's eps * m * max(c) bound, which
# holds under guaranteed=True (the solver runs at eps / 3)
CERTIFIED = DispatchPolicy(guaranteed=True)
WANT = ("cost", "duals")


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or uncertified answer."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def report(phase: str, **fields) -> dict:
    print(f"[{phase}] {json.dumps(fields, default=_plain)}", flush=True)
    return fields


def _plain(x):
    return x.item() if hasattr(x, "item") else str(x)


def _memory(device=None) -> dict:
    """bytes_in_use / peak_bytes_in_use as the backend reports them (the
    CPU backend reports nothing)."""
    stats = (device or jax.devices()[0]).memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use")}


def device_info() -> dict:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


# --------------------------------------------------------------------------
# inputs, made from the seed (on the device where they are large)
# --------------------------------------------------------------------------

def uniform_points(seed: int, n: int, d: int = 2):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.uniform(size=(n, d)), jnp.float32),
            jnp.asarray(rng.uniform(size=(n, d)), jnp.float32))


@jax.jit
def _unit_euclidean(x, y):
    c = euclidean(x, y)
    return c / jnp.max(c)


def assignment_instance(seed: int, n: int):
    """The paper's synthetic input: uniform points in the unit square,
    Euclidean cost scaled to max 1."""
    return _unit_euclidean(*uniform_points(seed, n))


def grid_ot_instance(seed: int, side: int):
    """DOTmark WhiteNoise pair on a side x side grid: i.i.d. uniform pixel
    masses, each image normalised to total mass 1, squared-Euclidean
    ground cost between grid points in the unit square."""
    n = side * side
    rng = np.random.default_rng(seed)
    nu = rng.uniform(size=n).astype(np.float32)
    mu = rng.uniform(size=n).astype(np.float32)
    idx = jnp.arange(n, dtype=jnp.int32)
    pts = (jnp.stack([idx // side, idx % side], axis=1).astype(jnp.float32)
           / max(side - 1, 1))
    c = jax.jit(sqeuclidean)(pts, pts)
    return c, jnp.asarray(nu / nu.sum()), jnp.asarray(mu / mu.sum())


def heavy_tailed_sizes(rng, k: int, lo: int, hi: int) -> np.ndarray:
    """Pareto(1.16) sizes in [lo, hi] (the 80/20 tail); the two extremes
    are always present so both end buckets are exercised."""
    n = np.clip(lo * (1.0 + rng.pareto(1.16, size=k)), lo, hi).astype(int)
    n[0], n[-1] = lo, hi
    return n


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def gate() -> dict:
    """Fail unless the default backend is a TPU and the Pallas kernels run
    compiled there."""
    from repro.kernels.slack_propose import _resolve_interpret

    backend = jax.default_backend()
    check(backend == "tpu",
          f"JAX found no TPU (default backend {backend!r}); this smoke run "
          "needs the chip")
    check(_resolve_interpret(None) is False,
          "Pallas kernels would run in interpret mode on this backend")
    return device_info()


def certify(sol, what: str) -> dict:
    gap, bound = sol.additive_gap(), sol.additive_gap_bound()
    feasible = sol.dual_feasible()
    check(feasible, f"{what}: duals are not eps-feasible")
    check(gap <= bound, f"{what}: additive gap {gap} exceeds the bound "
                        f"{bound}")
    return {"cost": sol.cost, "additive_gap": gap,
            "additive_gap_bound": bound, "dual_feasible": feasible}


def _timed_solve(spec, inputs, eps):
    """One warm-up solve, then one timed solve ending in a host fetch."""
    solve(spec, inputs, eps, CERTIFIED, want=WANT)[0].cost
    t0 = time.perf_counter()
    sol = solve(spec, inputs, eps, CERTIFIED, want=WANT)[0]
    sol.cost
    return sol, time.perf_counter() - t0


def phase_assignment(seed: int, n: int = 10_000, eps: float = 0.1) -> dict:
    c = assignment_instance(seed, n)
    sol, wall = _timed_solve(ASSIGNMENT, {"c": c[None]}, eps)
    cert = certify(sol, f"assignment n={n}")
    return report("assignment", n=n, eps=eps, wall_s=wall,
                  phases=sol.phases, rounds=sol.rounds, **cert, **_memory())


def phase_ot(seed: int, side: int = 128, eps: float = 0.1) -> dict:
    c, nu, mu = grid_ot_instance(seed, side)
    sol, wall = _timed_solve(OT, {"c": c[None], "nu": nu[None],
                                  "mu": mu[None]}, eps)
    cert = certify(sol, f"ot n={side * side}")
    return report("ot", n=side * side, grid=f"{side}x{side}", eps=eps,
                  wall_s=wall, phases=sol.phases, rounds=sol.rounds, **cert,
                  **_memory())


def phase_reference(seed: int, n: int = 256, eps: float = 0.1) -> dict:
    """Each cost within its own additive_gap_bound of the exact optimum."""
    c = assignment_instance(seed, n)
    a = solve(ASSIGNMENT, {"c": c[None]}, eps, CERTIFIED, want=WANT)[0]
    a_opt = exact.exact_assignment_cost(np.asarray(c))
    side = int(round(np.sqrt(n)))
    check(side * side == n, f"reference n={n} is not a square grid")
    c2, nu, mu = grid_ot_instance(seed, side)
    o = solve(OT, {"c": c2[None], "nu": nu[None], "mu": mu[None]}, eps,
              CERTIFIED, want=WANT)[0]
    o_opt = exact.exact_ot_cost(np.asarray(c2), np.asarray(nu),
                                np.asarray(mu))
    out = {}
    for name, sol, opt in (("assignment", a, a_opt), ("ot", o, o_opt)):
        bound = sol.additive_gap_bound()
        excess = sol.cost - opt
        # a primal answer may undercut the optimum only by float rounding
        check(-1e-4 * max(1.0, abs(opt)) <= excess <= bound,
              f"reference {name} n={n}: cost {sol.cost} vs exact {opt} "
              f"(bound {bound})")
        out[name] = {"cost": sol.cost, "exact": opt, "excess": excess,
                     "additive_gap_bound": bound}
    return report("reference", n=n, eps=eps, **out)


def phase_serving(seed: int, requests: int = 32, lo: int = 64,
                  hi: int = 2048, timeout_s: float = 900.0) -> dict:
    """Half OT, half assignment through the async scheduler's default
    mesh policy; every Future must resolve to a certified Solution on the
    configured rung, first attempt."""
    from repro.core.solution import Solution
    from repro.serve.scheduler import AsyncOTScheduler

    rng = np.random.default_rng(seed)
    sizes = heavy_tailed_sizes(rng, requests, lo, hi)
    eps = rng.choice([0.1, 0.05], size=requests)
    mesh = make_batch_mesh()
    policy = DispatchPolicy(mode="mesh", mesh=mesh, guaranteed=True)
    sched = AsyncOTScheduler(mesh=mesh, policy=policy, want=WANT)
    try:
        t0 = time.perf_counter()
        futs = []
        for i, n in enumerate(sizes):
            x = rng.uniform(size=(n, 2)).astype(np.float32)
            y = rng.uniform(size=(n, 2)).astype(np.float32)
            if i % 2:
                nu = rng.dirichlet(np.ones(n)).astype(np.float32)
                mu = rng.dirichlet(np.ones(n)).astype(np.float32)
                futs.append(sched.submit(x, y, nu, mu, eps=float(eps[i])))
            else:
                futs.append(sched.submit(x, y, eps=float(eps[i])))
        sols = [f.result(timeout=timeout_s) for f in futs]
        wall = time.perf_counter() - t0
        for i, sol in enumerate(sols):
            what = f"request {i} (n={sizes[i]}, eps={eps[i]})"
            check(isinstance(sol, Solution), f"{what}: resolved to "
                                             f"{type(sol).__name__}")
            check(sol.stats.ladder_level == 0 and sol.stats.attempts == 1,
                  f"{what}: served on ladder level {sol.stats.ladder_level} "
                  f"after {sol.stats.attempts} attempts")
            check(not sol.degraded, f"{what}: degraded answer")
            certify(sol, what)
        st = sched.stats_dict()
    finally:
        sched.close()
    for key in ("retries", "degraded", "rejected", "quarantined"):
        check(not st.get(key), f"serving: scheduler counted {key}="
                               f"{st.get(key)}")
    return report("serving", requests=requests, n_min=int(sizes.min()),
                  n_max=int(sizes.max()), wall_s=wall, kernel=sched.kernel,
                  batches=st.get("batches"), dispatches=st.get("dispatches"),
                  max_additive_gap_ratio=max(
                      s.additive_gap() / s.additive_gap_bound()
                      for s in sols))


def phase_kernels(seed: int, n: int = 1024, batch: int = 4) -> dict:
    """Every pallas_call entry point once against its reference: the
    kernels/ref.py oracles (float kernels to tolerance, integer kernels
    exactly) and the stepped cores for the fused phase kernels (integer
    state bit-identical)."""
    from repro.core.pushrelabel import (
        _max_phases, assignment_prologue, init_assignment_state,
        run_assignment_phases,
    )
    from repro.core.transport import (
        init_ot_state, ot_phase_cap, ot_prologue, ot_termination_threshold,
        run_ot_phases,
    )
    from repro.kernels import ops, ref

    rng = np.random.default_rng(seed)
    out = {}

    def close_to(name, got, want, rtol, atol):
        # oracles at full f32 matmul precision: the kernel's reference
        # must not be the less accurate side
        err = float(jnp.max(jnp.abs(got - want)))
        tol = atol + rtol * float(jnp.max(jnp.abs(want)))
        check(err <= tol, f"kernel {name}: max error {err} > {tol}")
        out[name] = {"max_abs_err": err}

    def same(name, got, want):
        for g, w in zip(got, want):
            check(np.array_equal(np.asarray(g), np.asarray(w)),
                  f"kernel {name}: differs from its reference")
        out[name] = {"exact": True}

    x = jnp.asarray(rng.uniform(size=(batch, n, 2)), jnp.float32)
    y = jnp.asarray(rng.uniform(size=(batch, n, 2)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        c_ref = jax.vmap(ref.cost_matrix_ref)(x, y)
    close_to("cost_matrix", ops.cost_matrix(x[0], y[0]), c_ref[0],
             1e-5, 1e-5)
    close_to("cost_matrix_batched", ops.cost_matrix_batched(x, y), c_ref,
             1e-5, 1e-5)

    c_int = jnp.asarray(rng.integers(0, 6, size=(batch, n, n)), jnp.int32)
    y_b = jnp.asarray(rng.integers(0, 4, size=(batch, n)), jnp.int32)
    y_a = -jnp.asarray(rng.integers(0, 4, size=(batch, n)), jnp.int32)
    avail = jnp.asarray(rng.uniform(size=(batch, n)) < 0.6)
    salt = jnp.arange(batch, dtype=jnp.int32) * 7919 + 3
    same("slack_propose",
         ops.slack_propose(c_int[0], y_b[0], y_a[0], avail[0], salt[0]),
         ref.slack_propose_ref(c_int[0], y_b[0], y_a[0], avail[0], salt[0]))
    same("slack_propose_batched",
         ops.slack_propose_batched(c_int, y_b, y_a, avail, salt),
         jax.vmap(ref.slack_propose_ref)(c_int, y_b, y_a, avail, salt))

    cf = c_ref[0]
    g = jnp.asarray(rng.normal(size=n) * 0.1, jnp.float32)
    log_nu = jnp.full((n,), -np.log(n), jnp.float32)
    reg = jnp.float32(0.05)
    close_to("sinkhorn_row_update", ops.sinkhorn_row_update(cf, g, log_nu,
                                                            reg),
             ref.sinkhorn_row_ref(cf, g, log_nu, reg), 1e-5, 1e-5)

    # eps 0.02: both instances then need more than one k-phase chunk, so
    # the check covers a resumed chunk as well as convergence
    eps, k = 0.02, 8
    _, ca, _, _, _ = assignment_prologue(assignment_instance(seed, n), eps)
    thr, cap = jnp.int32(int(eps * n)), jnp.int32(_max_phases(eps, n))
    s_ref, s_fus = init_assignment_state(n, n), init_assignment_state(n, n)
    for _ in range(2):
        s_ref = run_assignment_phases(ca, s_ref, thr, cap, k)
        s_fus = ops.fused_run_assignment_phases(ca, s_fus, thr, cap, k)
    same("fused_assignment_phases", s_fus, s_ref)
    out["fused_assignment_phases"]["phases"] = int(s_ref.phases)

    side = int(round(np.sqrt(n)))
    c2, nu, mu = grid_ot_instance(seed, side)
    theta = np.float32(4.0 * side * side / eps)
    co, s_int, d_int, _ = ot_prologue(c2, nu, mu, theta, eps)
    thr = jnp.int32(ot_termination_threshold(np.asarray(nu), theta, eps))
    cap, mr = jnp.int32(ot_phase_cap(eps)), 2 * side * side + 2
    s_ref, s_fus = init_ot_state(s_int, d_int), init_ot_state(s_int, d_int)
    for _ in range(2):
        s_ref = run_ot_phases(co, s_ref, thr, cap, k, mr)
        s_fus = ops.fused_run_ot_phases(co, s_fus, thr, cap, k, mr)
    same("fused_ot_phases", s_fus, s_ref)
    out["fused_ot_phases"]["phases"] = int(s_ref.phases)
    return report("kernels", n=n, fused_ot_n=side * side, **out)


def dev0_excess(per_device) -> int | None:
    """Device 0's peak bytes beyond the largest peak among the other
    devices (None where the backend reports no memory)."""
    peaks = [m["peak_bytes_in_use"] for m in per_device]
    if None in peaks:
        return None
    return peaks[0] - max(peaks[1:])


def _ot_batch(rng, sizes):
    batch = []
    for n in sizes:
        xs, ys = uniform_points(int(rng.integers(1 << 30)), int(n))
        batch.append((np.asarray(euclidean(xs, ys)),
                      rng.dirichlet(np.ones(n)).astype(np.float32),
                      rng.dirichlet(np.ones(n)).astype(np.float32)))
    return batch


def _batch_diffs(sharded, single, sizes) -> list:
    """Instances whose batch-placement answer is not bit-identical to the
    single-device one, or is not certified."""
    diffs = []
    for i, (a, b) in enumerate(zip(sharded, single)):
        fields = ([] if a.cost == b.cost else [f"cost {a.cost} vs {b.cost}"])
        fields += [name for name, u, v in zip(("y_b", "y_a"), a.duals(),
                                               b.duals())
                   if not np.array_equal(u, v)]
        if not np.array_equal(a.plan(), b.plan()):
            fields.append("plan")
        if a.phases != b.phases:
            fields.append(f"phases {a.phases} vs {b.phases}")
        try:
            certify(a, "certificate")
        except SmokeFailure as e:
            fields.append(str(e))
        if fields:
            diffs.append(f"instance {i} (n={sizes[i]}): {', '.join(fields)}")
    return diffs


def phase_four_chips(seed: int, side: int = 128, requests: int = 32,
                     lo: int = 64, hi: int = 512, spread_n: int = 1024,
                     eps: float = 0.1) -> dict:
    """The two four-chip placements against their single-device
    counterparts: batch placement bit for bit, matrix placement integer
    exact with floats to 1e-6. Device peaks are read after each sharded
    solve and before any single-device solve (a peak never falls)."""
    mesh = make_batch_mesh(4)
    devices = list(mesh.devices.flat)
    check(len(devices) == 4, f"need 4 devices, found {len(devices)}")
    rng = np.random.default_rng(seed)
    want = WANT + ("plan",)
    batch_policy = DispatchPolicy(mode="mesh", mesh=mesh, placement="batch",
                                  guaranteed=True)

    # 1. requests OT instances of one size, batch-sharded: each device must
    # hold its share of the lanes, and device 0 no more than two copies of
    # the batch's costs beyond the others (the front door stacks the bucket
    # there and keeps it for the certificate; the mesh driver masks a staging
    # copy before sharding it)
    uniform = _ot_batch(rng, [spread_n] * requests)
    t0 = time.perf_counter()
    spread = solve(OT, uniform, eps, batch_policy, want=want)
    for s in spread:
        s.cost
    spread_wall = time.perf_counter() - t0
    spread_mem = [{"device": d.id, **_memory(d)} for d in devices]
    share = requests // 4 * spread_n ** 2 * 4 * 2       # its c and c_int
    staged = 2 * requests * spread_n ** 2 * 4
    excess = dev0_excess(spread_mem)
    check(all(m["peak_bytes_in_use"] is None
              or m["peak_bytes_in_use"] >= share for m in spread_mem),
          f"batch placement left a device short of its share ({share} "
          f"bytes): {spread_mem}")
    check(excess is None or excess <= staged,
          f"batch placement piled {excess} bytes on device "
          f"{devices[0].id} beyond the other devices (staged costs "
          f"{staged}): {spread_mem}")

    # 2. a ragged heavy-tailed batch, batch-sharded
    sizes = heavy_tailed_sizes(rng, requests, lo, hi)
    eps_b = rng.choice([0.1, 0.05], size=requests)
    ragged = _ot_batch(rng, sizes)
    t0 = time.perf_counter()
    sharded = solve(OT, ragged, eps_b, batch_policy, want=want)
    for s in sharded:
        s.cost
    batch_wall = time.perf_counter() - t0
    devs_used = max(s.stats.devices for s in sharded)

    # 3. one large instance, row/col-sharded over the 2 x 2 mesh
    c, nu, mu = grid_ot_instance(seed, side)
    inputs = {"c": c[None], "nu": nu[None], "mu": mu[None]}
    t0 = time.perf_counter()
    mat = solve(OT, inputs, eps, DispatchPolicy(
        mode="mesh", mesh=mesh, placement="matrix", guaranteed=True),
        want=WANT + ("state",))
    check(mat.stats.placement == "matrix", "matrix placement not taken")
    mat_cert = certify(mat[0], f"matrix placement n={side * side}")
    got = jax.device_get((mat.state(), mat.duals(), mat.cost()))
    mat_wall = time.perf_counter() - t0
    del mat
    matrix_mem = [{"device": d.id, **_memory(d)} for d in devices]
    # each device must at least have held its quarter of the int32 costs;
    # the float prologue and epilogue run whole on device 0 (reported, not
    # refused: see matrix_dev0_excess_bytes)
    shard = side ** 4
    check(all(m["peak_bytes_in_use"] is None
              or m["peak_bytes_in_use"] >= shard for m in matrix_mem),
          f"matrix placement left a device short of its shard "
          f"({shard} bytes): {matrix_mem}")

    # single-device counterparts
    single = solve(OT, uniform, eps, CERTIFIED, want=want)
    diffs = _batch_diffs(spread, single, [spread_n] * requests)
    del spread, single
    single = solve(OT, ragged, eps_b, CERTIFIED, want=want)
    diffs += _batch_diffs(sharded, single, sizes)
    check(not diffs, "batch placement differs from single-device "
                     "compaction: " + "; ".join(diffs))
    del sharded, single
    t0 = time.perf_counter()
    one = solve(OT, inputs, eps, CERTIFIED, want=WANT + ("state",))
    expect = jax.device_get((one.state(), one.duals(), one.cost()))
    one_wall = time.perf_counter() - t0
    del one
    for f, a, b in zip(got[0]._fields, got[0], expect[0]):
        check(np.array_equal(a, b), f"matrix placement: state.{f} differs "
                                    "from the single-device solve")
    for name, a, b in (("y_b", got[1][0], expect[1][0]),
                       ("y_a", got[1][1], expect[1][1]),
                       ("cost", got[2], expect[2])):
        err = float(np.max(np.abs(a - b)))
        check(err <= 1e-6, f"matrix placement: {name} off by {err}")
    return report("four_chips",
                  batch_uniform={"requests": requests, "n": spread_n,
                                 "wall_s": spread_wall,
                                 "dev0_excess_bytes": excess,
                                 "bytes_per_device": spread_mem},
                  batch_ragged={"requests": requests,
                                "n_max": int(sizes.max()),
                                "wall_s": batch_wall, "devices": devs_used},
                  matrix={"n": side * side, "wall_s": mat_wall,
                          "single_device_wall_s": one_wall,
                          "dev0_excess_bytes": dev0_excess(matrix_mem),
                          "bytes_per_device": matrix_mem, **mat_cert})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip placement phase")
    args = ap.parse_args(argv)
    try:
        dev = gate()
        report("gate", **dev, compile_cache=use_compile_cache())
        if args.chips == 4:
            phase_four_chips(args.seed)
        else:
            phase_assignment(args.seed)
            phase_ot(args.seed)
            phase_reference(args.seed)
            phase_serving(args.seed)
            phase_kernels(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    except Exception:  # noqa: BLE001 - any crash is a failed smoke run
        traceback.print_exc()
        print("chip_smoke: FAILED with an exception", file=sys.stderr,
              flush=True)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
