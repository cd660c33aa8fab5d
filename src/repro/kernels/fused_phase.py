"""Pallas kernel: a full k-phase push-relabel dispatch with state in VMEM.

The stepped cores (``core/pushrelabel.run_assignment_phases`` /
``core/transport.run_ot_phases``) round-trip the solver state through
XLA/HBM between the slack/propose kernel and the push/relabel updates on
every propose round. This kernel fuses the whole chunk — slack +
propose/accept + push + relabel, for up to ``k`` phases — into ONE
``pallas_call``: the state (duals, matching/flows, free mask) is read into
VMEM registers once, the nested phase/round ``lax.while_loop``s run inside
the kernel body, and the state is written back exactly once per dispatch.

Bit parity with the stepped cores is a hard contract (the compacting and
mesh drivers interleave fused and stepped programs freely), which pins
three things:

  * the hash is the identical ``_mix`` chain over the identical
    ``row * H1 + col * H2 + salt_round * H3`` preimage, with
    ``salt_round = phases * 7919 + round`` (constants shared with
    ``core/matching`` / ``kernels/slack_propose``);
  * scatter/gather steps of the stepped cores are re-expressed as dense
    one-hot reductions with *identical* tie-breaking: ``argmin`` becomes
    min-key + first-min-index, per-column winner selection becomes a
    masked row-iota min, and the OT FIFO grant prefix becomes a one-hot
    masked min of the exclusive row cumsum;
  * round/phase caps come from the LOGICAL (pre-tile-padding) shape, so
    the loop trip counts equal the stepped cores' exactly.

Tile padding: inputs are padded up to (block_m, block_n) multiples before
the call (whole-array blocks — the k-phase loop needs every tile resident,
so block sizes here choose the *pad granularity*, aligning the arrays to
the backend's native tile). Padded rows carry zero supply/free mass and
padded columns are never admissible (``avail = 0`` / zero capacity +
``PAD_COST``), the same born-inert convention the bucketed batch drivers
use, so the padded trajectory equals the unpadded one bit for bit.

The kernel is shape-generic per instance; the batch grid comes from the
drivers ``vmap``-ing the jitted wrappers in ``kernels/ops.py`` (exactly
how ``slack_propose_batched`` acquires its leading grid axis).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .slack_propose import (
    _H1, _H2, _H3, _I32_MAX, _mix, _resolve_interpret, _signed_keys,
)

# Scoped VMEM granted to the fused kernels (the compiler's default is
# 16 MiB); a v5e core has 128 MiB.
VMEM_LIMIT_BYTES = 100 * 2**20

# Largest padded instance (rows x cols) each kernel holds within
# VMEM_LIMIT_BYTES, from compiles for a described v5e: OT fits at
# 1024 x 1024 and not at 1280 x 1280; assignment fits at 2048 x 2048.
MAX_CELLS = {"assignment": 2048 * 2048, "ot": 1024 * 1024}


def check_fits(kind: str, rows: int, cols: int, interpret: bool) -> None:
    """Raise ``ValueError`` unless a padded ``rows x cols`` instance of
    ``kind`` fits the fused kernel's VMEM. Only a compiled (Mosaic) kernel
    has that limit; interpret mode runs at any size. Runs while tracing, so
    a shape the chip cannot hold is refused before anything is
    dispatched."""
    if not interpret and rows * cols > MAX_CELLS[kind]:
        raise ValueError(
            f"fused {kind} kernel: a {rows} x {cols} (padded) instance "
            f"exceeds its VMEM budget of {MAX_CELLS[kind]} cells "
            f"({VMEM_LIMIT_BYTES >> 20} MiB); solve it with the stepped "
            "core (DispatchPolicy(fused=False))")


# Sentinel cost for tile-padded edges; must match core.pushrelabel.PAD_COST
# (duals can never sum to it, so padded edges are never admissible).
_PAD_COST = 1 << 26


def _iotas(mp: int, np_: int):
    row_i = jax.lax.broadcasted_iota(jnp.int32, (mp, 1), 0)
    col_i = jax.lax.broadcasted_iota(jnp.int32, (1, np_), 1)
    return row_i, col_i


def _keys(row_u, col_u, salt_round):
    """``matching.proposal_keys`` under the order-preserving int32 map, so
    the min-reductions below pick the same winners."""
    return _signed_keys(_mix(row_u + col_u + salt_round.astype(jnp.uint32)
                             * jnp.uint32(_H3)))


def _first_min_col(keys, col_i, col_real, np_: int):
    """First column index attaining the row-min key, restricted to logical
    columns — ``jnp.argmin(keys, axis=1)`` re-expressed without gather
    (padded columns hold I32_MAX so they never beat a logical min, and the
    ``col_real`` mask keeps them out of the index min even on all-I32_MAX
    rows, where argmin's first-min falls on column 0)."""
    rowmin = jnp.min(keys, axis=1, keepdims=True)
    return jnp.min(
        jnp.where((keys == rowmin) & col_real, col_i, jnp.int32(np_)),
        axis=1, keepdims=True,
    )


def _cumsum_rows(x, row_i):
    """Inclusive prefix sum along axis 0 (``jnp.cumsum(x, axis=0)``) as a
    log-step scan of sublane rotations: Mosaic has no cumsum. Integer
    adds, so the result is exact in any order."""
    shift = 1
    while shift < x.shape[0]:
        x = x + jnp.where(row_i >= shift, pltpu.roll(x, shift, 0), 0)
        shift *= 2
    return x


# --------------------------------------------------------------------------
# Assignment (Algorithm 1): k phases of matching + push + relabel
# --------------------------------------------------------------------------


def _assignment_kernel(c_ref, mba_ref, mab_ref, yb_ref, ya_ref, scal_ref,
                       mba_out, mab_out, yb_out, ya_out, scal_out,
                       *, m: int, n: int, k: int):
    mp, np_ = c_ref.shape
    c = c_ref[...]
    scal = scal_ref[...]
    phases0, rounds0, sum0 = scal[0, 0], scal[0, 1], scal[0, 2]
    threshold, phase_cap, m_valid = scal[0, 3], scal[0, 4], scal[0, 5]

    row_i, col_i = _iotas(mp, np_)
    row_ok = row_i < m_valid            # m_valid <= m: tile pad rows excluded
    col_real = col_i < n
    row_u = row_i.astype(jnp.uint32) * jnp.uint32(_H1)
    col_u = col_i.astype(jnp.uint32) * jnp.uint32(_H2)
    mm_cap = jnp.int32(min(m, n) + 1)   # logical-shape round cap
    start = phases0

    def phase_cond(s):
        mba, _, _, _, phases, _, _ = s
        free = jnp.sum(((mba < 0) & row_ok).astype(jnp.int32))
        return ((free > threshold) & (phases < phase_cap)
                & (phases - start < jnp.int32(k)))

    def phase_body(s):
        mba, mab, yb, ya, phases, rounds, sum_ni = s
        in_bp = (mba < 0) & row_ok                        # B' (mp, 1)

        # (I) greedy maximal matching M' (matching.greedy_maximal_matching)
        def mm_cond(t):
            _, _, _, r, done = t
            return (~done) & (r < mm_cap)

        # avail / active ride the loop as int32 0/1: Mosaic cannot carry
        # boolean vectors through a loop
        def mm_body(t):
            mpb, avail, active, r, _ = t
            keys = _keys(row_u, col_u, phases * jnp.int32(7919) + r)
            adm = (yb + ya == c + 1) & (avail != 0)
            keys = jnp.where(adm, keys, jnp.int32(_I32_MAX))
            best = _first_min_col(keys, col_i, col_real, np_)
            has_prop = jnp.any(adm, axis=1, keepdims=True) & (active != 0)
            prop = has_prop & (best == col_i)             # one-hot proposals
            # accept: per column, lowest-index proposing row wins
            winners = jnp.min(jnp.where(prop, row_i, jnp.int32(mp)),
                              axis=0, keepdims=True)
            won_edge = prop & (winners == row_i)
            won = jnp.any(won_edge, axis=1, keepdims=True)
            taken = jnp.any(won_edge, axis=0, keepdims=True)
            return (jnp.where(won, best, mpb), jnp.where(taken, 0, avail),
                    jnp.where(won, 0, active), r + 1, ~jnp.any(has_prop))

        mpb, _, _, mm_rounds, _ = jax.lax.while_loop(
            mm_cond, mm_body,
            (jnp.full((mp, 1), -1, jnp.int32), col_real.astype(jnp.int32),
             in_bp.astype(jnp.int32), jnp.int32(0), jnp.bool_(False)),
        )

        # (II) push: add M' to M, displacing old partners of M' columns
        won = mpb >= 0
        newmat = won & (mpb == col_i)                     # one-hot M'
        col_new = jnp.any(newmat, axis=0, keepdims=True)
        displaced = (mba >= 0) & jnp.any((mba == col_i) & col_new,
                                         axis=1, keepdims=True)
        mba = jnp.where(won, mpb,
                        jnp.where(displaced, jnp.int32(-1), mba))
        new_row = jnp.min(jnp.where(newmat, row_i, jnp.int32(mp)),
                          axis=0, keepdims=True)
        mab = jnp.where(col_new, new_row, mab)
        # (III) relabel
        ya = ya - col_new.astype(jnp.int32)
        yb = yb + (in_bp & ~won).astype(jnp.int32)
        return (mba, mab, yb, ya, phases + 1, rounds + mm_rounds,
                sum_ni + jnp.sum(in_bp.astype(jnp.int32)))

    mba, mab, yb, ya, phases, rounds, sum_ni = jax.lax.while_loop(
        phase_cond, phase_body,
        (mba_ref[...], mab_ref[...], yb_ref[...], ya_ref[...],
         phases0, rounds0, sum0),
    )
    mba_out[...] = mba
    mab_out[...] = mab
    yb_out[...] = yb
    ya_out[...] = ya
    scal_out[...] = jnp.stack(
        [phases, rounds, sum_ni, threshold, phase_cap, m_valid,
         jnp.int32(0), jnp.int32(0)]
    ).reshape(1, 8)


def _pad2(x, mp, np_, value):
    m, n = x.shape
    if (m, n) == (mp, np_):
        return x
    return jnp.pad(x, ((0, mp - m), (0, np_ - n)), constant_values=value)


def fused_assignment_phases(
    c_int, match_ba, match_ab, y_b, y_a, phases, rounds, sum_ni,
    threshold, phase_cap, m_valid, *, k: int,
    block_m: int = 8, block_n: int = 128, interpret: bool | None = None,
):
    """At most ``k`` assignment phases in one fused kernel launch.

    Array arguments are the ``PushRelabelState`` fields plus the traced
    termination operands; returns the updated fields in the same order
    (the jitted wrapper in ``kernels/ops.py`` re-wraps the NamedTuple).
    Bit-identical to chaining ``assignment_phase`` for every ``k``.
    """
    m, n = c_int.shape
    mp = m + (-m) % block_m
    np_ = n + (-n) % block_n
    interpret = _resolve_interpret(interpret)
    check_fits("assignment", mp, np_, interpret)
    c_p = _pad2(c_int, mp, np_, _PAD_COST)
    mba_p = jnp.pad(match_ba, (0, mp - m),
                    constant_values=-1).reshape(mp, 1)
    yb_p = jnp.pad(y_b, (0, mp - m)).reshape(mp, 1)
    mab_p = jnp.pad(match_ab, (0, np_ - n),
                    constant_values=-1).reshape(1, np_)
    ya_p = jnp.pad(y_a, (0, np_ - n)).reshape(1, np_)
    scal = jnp.stack([
        jnp.asarray(phases, jnp.int32), jnp.asarray(rounds, jnp.int32),
        jnp.asarray(sum_ni, jnp.int32), jnp.asarray(threshold, jnp.int32),
        jnp.asarray(phase_cap, jnp.int32), jnp.asarray(m_valid, jnp.int32),
        jnp.int32(0), jnp.int32(0),
    ]).reshape(1, 8)
    i32 = jnp.int32
    mba, mab, yb, ya, scal = pl.pallas_call(
        functools.partial(_assignment_kernel, m=m, n=n, k=k),
        out_shape=[
            jax.ShapeDtypeStruct((mp, 1), i32),
            jax.ShapeDtypeStruct((1, np_), i32),
            jax.ShapeDtypeStruct((mp, 1), i32),
            jax.ShapeDtypeStruct((1, np_), i32),
            jax.ShapeDtypeStruct((1, 8), i32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
    )(c_p, mba_p, mab_p, yb_p, ya_p, scal)
    return (mba[:m, 0], mab[0, :n], yb[:m, 0], ya[0, :n],
            scal[0, 0], scal[0, 1], scal[0, 2])


# --------------------------------------------------------------------------
# General OT (Algorithm 2): k phases of capacity grants + push + relabel
# --------------------------------------------------------------------------


def _ot_kernel(c_ref, yb_ref, yahi_ref, fb_ref, fa_ref, fhi_ref, flo_ref,
               scal_ref, yb_out, yahi_out, fb_out, fa_out, fhi_out,
               flo_out, scal_out, *, n: int, k: int, max_rounds: int):
    nbp, nap = c_ref.shape
    c = c_ref[...]
    scal = scal_ref[...]
    phases0, rounds0 = scal[0, 0], scal[0, 1]
    threshold, phase_cap = scal[0, 2], scal[0, 3]

    row_i, col_i = _iotas(nbp, nap)
    col_real = col_i < n
    row_u = row_i.astype(jnp.uint32) * jnp.uint32(_H1)
    col_u = col_i.astype(jnp.uint32) * jnp.uint32(_H2)
    big = jnp.int32(_I32_MAX)
    start = phases0

    def phase_cond(s):
        _, _, fb, _, _, _, phases, _ = s
        free = jnp.sum(fb)
        return ((free > threshold) & (phases < phase_cap)
                & (phases - start < jnp.int32(k)))

    def phase_body(s):
        yb, yahi, fb, fa, fhi, flo, phases, rounds = s
        # hi-cluster capacity available to M' (transport._phase)
        cap0 = jnp.where(yahi == 0, fa, 0) + jnp.sum(fhi, axis=0,
                                                     keepdims=True)

        def g_cond(t):
            _, _, _, r, done = t
            return (~done) & (r < jnp.int32(max_rounds))

        def g_body(t):
            rem, cap, granted, r, _ = t
            keys = _keys(row_u, col_u, phases * jnp.int32(7919) + r)
            adm = (yb + yahi == c + 1) & (cap > 0)
            keys = jnp.where(adm, keys, jnp.int32(_I32_MAX))
            best = _first_min_col(keys, col_i, col_real, nap)
            can = jnp.any(adm, axis=1, keepdims=True) & (rem > 0)
            prop = can & (best == col_i)                # one-hot proposals
            # FIFO grants by row order: segmented exclusive prefix of the
            # proposal amounts (transport._grant_round), one-hot reduced
            amt = jnp.where(can, rem, 0)
            excl = _cumsum_rows(amt, row_i) - amt       # (nbp, 1)
            base = jnp.min(
                jnp.where(prop, jnp.broadcast_to(excl, (nbp, nap)), big),
                axis=0, keepdims=True)                  # per-col min excl
            base_t = jnp.min(jnp.where(prop, base, big), axis=1,
                             keepdims=True)             # base[tgt] per row
            cap_t = jnp.min(jnp.where(prop, cap, big), axis=1,
                            keepdims=True)              # cap_a[tgt] per row
            prefix = excl - jnp.where(can, base_t, 0)
            grant = jnp.where(can, jnp.clip(cap_t - prefix, 0, amt), 0)
            g_edge = jnp.where(prop, grant, 0)
            return (rem - grant,
                    cap - jnp.sum(g_edge, axis=0, keepdims=True),
                    granted + g_edge, r + 1, ~jnp.any(can))

        rem, _, granted, g_rounds, _ = jax.lax.while_loop(
            g_cond, g_body,
            (fb, cap0, jnp.zeros((nbp, nap), jnp.int32),
             jnp.int32(0), jnp.bool_(False)),
        )

        # push: displaced hi flow stripped bottom rows first
        g_a = jnp.sum(granted, axis=0, keepdims=True)
        use_free = jnp.minimum(g_a, jnp.where(yahi == 0, fa, 0))
        disp = g_a - use_free
        # suffix-exclusive column sums == reversed-cumsum form, exactly
        suffix_excl = (jnp.sum(fhi, axis=0, keepdims=True)
                       - _cumsum_rows(fhi, row_i))
        take = jnp.clip(disp - suffix_excl, 0, fhi)
        fhi2 = fhi - take
        freed = jnp.sum(take, axis=1, keepdims=True)

        # relabel: granted copies drop one level; empty hi clusters collapse
        fa2 = fa - use_free
        hi_left = (jnp.where(yahi == 0, fa2, 0)
                   + jnp.sum(fhi2, axis=0, keepdims=True))
        collapse = (hi_left == 0) & (g_a > 0)
        yahi2 = jnp.where(collapse, yahi - 1, yahi)
        fhi3 = jnp.where(collapse, flo + granted, fhi2)
        flo3 = jnp.where(collapse, 0, flo + granted)
        yb2 = yb + ((fb > 0) & (rem > 0)).astype(jnp.int32)
        return (yb2, yahi2, rem + freed, fa2, fhi3, flo3,
                phases + 1, rounds + g_rounds)

    yb, yahi, fb, fa, fhi, flo, phases, rounds = jax.lax.while_loop(
        phase_cond, phase_body,
        (yb_ref[...], yahi_ref[...], fb_ref[...], fa_ref[...],
         fhi_ref[...], flo_ref[...], phases0, rounds0),
    )
    yb_out[...] = yb
    yahi_out[...] = yahi
    fb_out[...] = fb
    fa_out[...] = fa
    fhi_out[...] = fhi
    flo_out[...] = flo
    scal_out[...] = jnp.stack(
        [phases, rounds, threshold, phase_cap,
         jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.int32(0)]
    ).reshape(1, 8)


def fused_ot_phases(
    c_int, y_b, ya_hi, free_b, free_a, f_hi, f_lo, phases, rounds,
    threshold, phase_cap, *, k: int, max_rounds: int,
    block_m: int = 8, block_n: int = 128, interpret: bool | None = None,
):
    """At most ``k`` OT phases in one fused kernel launch; array arguments
    are the ``OTState`` fields. Bit-identical to ``transport._phase``
    chained under the ``run_ot_phases`` guard for every ``k``."""
    nb, na = c_int.shape
    nbp = nb + (-nb) % block_m
    nap = na + (-na) % block_n
    interpret = _resolve_interpret(interpret)
    check_fits("ot", nbp, nap, interpret)
    c_p = _pad2(c_int, nbp, nap, _PAD_COST)
    yb_p = jnp.pad(y_b, (0, nbp - nb)).reshape(nbp, 1)
    fb_p = jnp.pad(free_b, (0, nbp - nb)).reshape(nbp, 1)
    yahi_p = jnp.pad(ya_hi, (0, nap - na)).reshape(1, nap)
    fa_p = jnp.pad(free_a, (0, nap - na)).reshape(1, nap)
    fhi_p = _pad2(f_hi, nbp, nap, 0)
    flo_p = _pad2(f_lo, nbp, nap, 0)
    scal = jnp.stack([
        jnp.asarray(phases, jnp.int32), jnp.asarray(rounds, jnp.int32),
        jnp.asarray(threshold, jnp.int32), jnp.asarray(phase_cap, jnp.int32),
        jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.int32(0),
    ]).reshape(1, 8)
    i32 = jnp.int32
    yb, yahi, fb, fa, fhi, flo, scal = pl.pallas_call(
        functools.partial(_ot_kernel, n=na, k=k, max_rounds=max_rounds),
        out_shape=[
            jax.ShapeDtypeStruct((nbp, 1), i32),
            jax.ShapeDtypeStruct((1, nap), i32),
            jax.ShapeDtypeStruct((nbp, 1), i32),
            jax.ShapeDtypeStruct((1, nap), i32),
            jax.ShapeDtypeStruct((nbp, nap), i32),
            jax.ShapeDtypeStruct((nbp, nap), i32),
            jax.ShapeDtypeStruct((1, 8), i32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
    )(c_p, yb_p, yahi_p, fb_p, fa_p, fhi_p, flo_p, scal)
    return (yb[:nb, 0], yahi[0, :na], fb[:nb, 0], fa[0, :na],
            fhi[:nb, :na], flo[:nb, :na], scal[0, 0], scal[0, 1])
