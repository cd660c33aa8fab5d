"""Checkify-instrumented variant of the chunked phase dispatch.

The solver cores are numerically silent by design: a NaN-poisoned cost
matrix rounds to garbage integers and the solve "converges" to nonsense;
a corrupted state (e.g. a buffer reused after donation — the PR-3 bug)
walks wild indices without complaint. This module mirrors
``compaction.spec_fns`` with the functional error checks of
``jax.experimental.checkify`` (nan / index / div) plus explicit
structural invariant checks per spec, so a debug run raises a useful
error at the first poisoned chunk instead of silently terminating.

Enabled through the driver: ``repro.analysis.set_debug_checks(True)`` (or
``REPRO_DEBUG_CHECKS=1``) makes ``solve_compacting`` dispatch these
functions. Differences from the production path, by construction:

  * the chunk dispatch does NOT donate the state (checkify rewrites the
    program; holding two copies in debug mode is the accepted cost);
  * every chunk ``err.throw()``s on host — one extra sync per chunk.
"""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
from jax.experimental import checkify

ERRORS = (checkify.user_checks | checkify.nan_checks
          | checkify.index_checks | checkify.div_checks)
# The phase loops and completion epilogues index with the sentinel value n
# ("no match") and rely on XLA's clamped gather semantics — auto
# index_checks would flag that benign idiom on every healthy chunk. The
# chunk and epilogue therefore run nan/div auto checks plus the EXPLICIT
# structural invariants below (which do catch corrupted indices:
# match_ba/match_ab must lie in [-1, n)); the rounding prologue, which has
# no sentinel gathers, gets the full auto set.
CHUNK_ERRORS = (checkify.user_checks | checkify.nan_checks
                | checkify.div_checks)


def _assignment_invariants(data, state):
    n = data["c_int"].shape[1]
    checkify.check(
        jnp.all((state.match_ba >= -1) & (state.match_ba < n)),
        "assignment matching index out of range: match_ba must lie in "
        "[-1, {n}) (corrupted state / donated-buffer reuse?)",
        n=jnp.int32(n),
    )
    m = data["c_int"].shape[0]
    checkify.check(
        jnp.all((state.match_ab >= -1) & (state.match_ab < m)),
        "assignment matching index out of range: match_ab must lie in "
        "[-1, {m}) (corrupted state / donated-buffer reuse?)",
        m=jnp.int32(m),
    )


def _ot_invariants(data, state):
    checkify.check(
        jnp.all(state.free_b >= 0) & jnp.all(state.free_a >= 0),
        "negative free mass in OT state (corrupted state / donated-buffer "
        "reuse?)",
    )
    checkify.check(
        jnp.all(state.f_hi >= 0) & jnp.all(state.f_lo >= 0),
        "negative flow in OT state (corrupted state / donated-buffer "
        "reuse?)",
    )


def _sinkhorn_invariants(data, state):
    checkify.check(
        jnp.all(jnp.isfinite(state.f)) & jnp.all(jnp.isfinite(state.g)),
        "non-finite Sinkhorn potentials (poisoned costs / corrupted "
        "state / donated-buffer reuse?)",
    )
    checkify.check(
        jnp.all(data["reg"] > 0),
        "non-positive Sinkhorn regularization (schedule corrupted?)",
    )


_INVARIANTS = {"assignment": _assignment_invariants, "ot": _ot_invariants,
               "warm_ot": _ot_invariants,
               "sinkhorn": _sinkhorn_invariants}


def _throwing(ck_fn):
    def wrapped(*args):
        err, out = ck_fn(*args)
        err.throw()
        return out
    return wrapped


def checkified_spec_fns(spec, k: int):
    """(prologue, init, chunk, conv, epilogue) mirroring
    ``compaction.spec_fns`` with checkify instrumentation on the
    prologue, chunk, and epilogue dispatches (init and the converged
    probe stay plain: they are pure shape/compare code). Same call
    signatures; the chunk does NOT donate.

    Fused specs route through their stepped base (BEFORE the cache, so
    fused and stepped share one instrumented program family): checkify
    cannot instrument the interior of a Pallas kernel (the state never
    surfaces between phases), and the fused trajectory is bit-identical
    to the stepped one (tests/test_fused_phase.py), so the stepped chunk
    checks exactly the states the fused kernel would produce."""
    return _checkified_spec_fns(getattr(spec, "stepped", spec), k)


@lru_cache(maxsize=None)
def _checkified_spec_fns(spec, k: int):
    from ..core.compaction import spec_fns
    from ..core.problem import lane_map

    _, init, _, conv, _ = spec_fns(spec, k)
    invariants = _INVARIANTS[spec.name]

    # Checkify goes inside the batching: it cannot rewrite a batched
    # while-loop (checkify-of-vmap-of-while). The error value comes out
    # stacked per lane and ``throw()`` reports the first failed lane's
    # message. The prologue is vmapped and the epilogue lane-mapped,
    # exactly as in production, so their float reductions match it bit
    # for bit. The chunk's lanes also run one after another: vmap-of-
    # checkify fails to batch the phase loop's nested while-loops, and the
    # chunk is integer-exact, so the lane order cannot change its result.
    def one(d, s):
        invariants(d, s)
        return spec.run_phases(d, s, k)

    ck_prologue = jax.jit(jax.vmap(
        checkify.checkify(spec.prologue, errors=ERRORS)))
    ck_chunk = jax.jit(lane_map(checkify.checkify(one, errors=CHUNK_ERRORS)))
    ck_epilogue = jax.jit(lane_map(
        checkify.checkify(spec.epilogue, errors=CHUNK_ERRORS)))

    return (_throwing(ck_prologue), init, _throwing(ck_chunk), conv,
            _throwing(ck_epilogue))
