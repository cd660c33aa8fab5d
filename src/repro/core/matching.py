"""Parallel greedy maximal matching via randomized propose/accept rounds.

This is step (I) of each push-relabel phase (the only non-O(1) parallel step).
Every free supply vertex ``b`` proposes to one *admissible* demand vertex ``a``
chosen by a per-(b, a, round) hash key (Israeli-Itai style randomization,
expected O(log n) rounds); every ``a`` accepts the lowest-index proposer.
Accepted pairs leave the pool; repeat until no proposals exist, at which point
the produced matching M' is maximal on the admissible subgraph.

Everything is integer-exact: admissibility is ``y_b + y_a == C + 1`` (tight
relaxed feasibility, in units of eps). All arrays live on device; the loop is
a ``lax.while_loop`` so the whole phase stays inside one XLA program.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

# Knuth/xxhash-style odd multipliers for the integer mix.
_H1 = jnp.uint32(2654435761)
_H2 = jnp.uint32(2246822519)
_H3 = jnp.uint32(3266489917)


def _mix(h: jnp.ndarray) -> jnp.ndarray:
    h = h ^ (h >> jnp.uint32(15))
    h = h * _H2
    h = h ^ (h >> jnp.uint32(13))
    h = h * _H3
    return h ^ (h >> jnp.uint32(16))


def proposal_keys(m: int, n: int, salt: jnp.ndarray) -> jnp.ndarray:
    """Deterministic pseudo-random uint32 key per (row, col) for one round."""
    rows = jnp.arange(m, dtype=jnp.uint32)[:, None]
    cols = jnp.arange(n, dtype=jnp.uint32)[None, :]
    return _mix(rows * _H1 + cols * _H2 + salt.astype(jnp.uint32) * _H3)


def vary_like(tree, *refs):
    """``tree`` marked varying over every ``shard_map`` axis any of ``refs``
    varies over; a no-op outside ``shard_map``.

    A ``while_loop`` whose initial carry is built from constants fails the
    varying-axes check when its body mixes in sharded operands: the carry
    leaves the body varying but entered it unvarying."""
    axes = frozenset().union(*(jax.typeof(r).vma for r in refs))

    def cast(x):
        missing = tuple(sorted(axes - jax.typeof(x).vma))
        return jax.lax.pcast(x, missing, to="varying") if missing else x

    return jax.tree.map(cast, tree)


class MaximalMatchingState(NamedTuple):
    mprime_b: jnp.ndarray   # (m,) int32: M' partner col per row, -1 if none
    mprime_a: jnp.ndarray   # (n,) int32: M' partner row per col, -1 if none
    avail_a: jnp.ndarray    # (n,) bool: col not yet matched in M'
    active_b: jnp.ndarray   # (m,) bool: row in B' not yet matched in M'
    rounds: jnp.ndarray     # () int32
    done: jnp.ndarray       # () bool


def greedy_maximal_matching(
    c_int: jnp.ndarray,
    y_b: jnp.ndarray,
    y_a: jnp.ndarray,
    in_bprime: jnp.ndarray,
    salt: jnp.ndarray,
    *,
    propose_fn=None,
) -> MaximalMatchingState:
    """Maximal matching M' on the admissible subgraph touching B'.

    Args:
      c_int: (m, n) int32 costs in units of eps.
      y_b: (m,) int32 supply duals (units of eps).
      y_a: (n,) int32 demand duals (units of eps).
      in_bprime: (m,) bool, rows that are free in M (the set B').
      salt: scalar int32 folded into the per-round hash (phase index).
      propose_fn: optional override computing per-row proposals; signature
        (c_int, y_b, y_a, active_b, avail_a, salt_round) -> (m,) int32 col or
        -1. Used to swap in the Pallas kernel.
    """
    m, n = c_int.shape
    if propose_fn is None:
        propose_fn = _propose_dense

    init = vary_like(MaximalMatchingState(
        mprime_b=jnp.full((m,), -1, jnp.int32),
        mprime_a=jnp.full((n,), -1, jnp.int32),
        avail_a=jnp.ones((n,), bool),
        active_b=in_bprime,
        rounds=jnp.int32(0),
        done=jnp.bool_(False),
    ), c_int, y_b, y_a, in_bprime, salt)

    def cond(s: MaximalMatchingState):
        return (~s.done) & (s.rounds < jnp.int32(min(m, n) + 1))

    def body(s: MaximalMatchingState) -> MaximalMatchingState:
        salt_round = salt * jnp.int32(7919) + s.rounds
        prop = propose_fn(c_int, y_b, y_a, s.active_b, s.avail_a, salt_round)
        has_prop = prop >= 0
        # Accept: per column, lowest-index proposing row wins.
        rows = jnp.arange(m, dtype=jnp.int32)
        sentinel = jnp.int32(m)
        tgt = jnp.where(has_prop, prop, 0)
        winners = jnp.full((n,), sentinel, jnp.int32).at[tgt].min(
            jnp.where(has_prop, rows, sentinel), mode="drop"
        )
        won = has_prop & (winners[tgt] == rows)
        new_col = jnp.where(won, prop, s.mprime_b)
        # Column-side bookkeeping for the pairs just matched. The drop
        # sentinel must be out of range for the COLUMN axis (n, not m).
        col_sentinel = jnp.int32(n)
        mprime_a = s.mprime_a.at[jnp.where(won, prop, col_sentinel)].set(
            rows, mode="drop"
        )
        avail_a = s.avail_a.at[jnp.where(won, prop, col_sentinel)].set(
            False, mode="drop"
        )
        return MaximalMatchingState(
            mprime_b=new_col,
            mprime_a=mprime_a,
            avail_a=avail_a,
            active_b=s.active_b & ~won,
            rounds=s.rounds + 1,
            done=~jnp.any(has_prop),
        )

    return jax.lax.while_loop(cond, body, init)


def _propose_dense(c_int, y_b, y_a, active_b, avail_a, salt_round):
    """Reference proposal step: dense masked hash-argmin over columns."""
    m, n = c_int.shape
    adm = (y_b[:, None] + y_a[None, :] == c_int + 1) & avail_a[None, :]
    keys = proposal_keys(m, n, salt_round)
    keys = jnp.where(adm, keys, jnp.uint32(0xFFFFFFFF))
    best = jnp.argmin(keys, axis=1).astype(jnp.int32)
    any_adm = jnp.any(adm, axis=1) & active_b
    return jnp.where(any_adm, best, jnp.int32(-1))
