"""Pallas TPU kernel: fused slack + admissibility + hash-random proposal.

This is the n^2 hot loop of every push-relabel phase. The reference path
materializes three (m, n) intermediates in HBM (slack, admissible mask,
proposal keys); this kernel streams cost tiles HBM->VMEM once and emits only
two (m,) vectors (winning column + winning hash key), i.e. it is a pure
min-reduction over the column axis with everything fused into the tile.

Tiling: grid (m/BM, n/BN); the column axis is the reduction axis, so the
output BlockSpec is constant in j and the accumulator pattern (@pl.when on
j == 0 / strict-less merge) gives exactly jnp.argmin's first-min semantics.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_H1 = 2654435761
_H2 = 2246822519
_H3 = 3266489917
_UMAX = 0xFFFFFFFF
_SIGN = 0x80000000
_I32_MAX = 0x7FFFFFFF   # == _signed_keys(_UMAX)


def _mix(h):
    h2 = jnp.uint32(_H2)
    h3 = jnp.uint32(_H3)
    h = h ^ (h >> jnp.uint32(15))
    h = h * h2
    h = h ^ (h >> jnp.uint32(13))
    h = h * h3
    return h ^ (h >> jnp.uint32(16))


def _signed_keys(h):
    """Order-preserving uint32 -> int32 map: flip the sign bit, then
    bitcast. Mosaic has no min-reduction over unsigned integers, so every
    key reduction runs on these; ``_UMAX`` maps to ``_I32_MAX``."""
    return jax.lax.bitcast_convert_type(h ^ jnp.uint32(_SIGN), jnp.int32)


def _unsigned_keys(k):
    """Inverse of ``_signed_keys``."""
    return jax.lax.bitcast_convert_type(k, jnp.uint32) ^ jnp.uint32(_SIGN)


def _tile_propose(c, yb, ya, avail, salt, i, j, bm: int, bn: int):
    """Shared tile body: fused slack + admissibility + hash-key argmin on
    one (bm, bn) tile at grid position (i, j). Returns the tile's winning
    (signed key, global col) per row, each (bm, 1). Both the unbatched and
    the batched kernel reduce these with the identical first-min
    accumulator, so the two stay bit-identical by construction."""
    rows_g = (i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)
              ).astype(jnp.uint32)
    cols_l = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
    cols_g = (j * bn + cols_l).astype(jnp.uint32)

    keys = _signed_keys(_mix(rows_g * jnp.uint32(_H1)
                             + cols_g * jnp.uint32(_H2)
                             + salt * jnp.uint32(_H3)))
    adm = (yb + ya == c + 1) & (avail != 0)
    keys = jnp.where(adm, keys, jnp.int32(_I32_MAX))

    # argmin's first-min as two min-reductions (Mosaic's argmin takes
    # float32 only)
    tile_key = jnp.min(keys, axis=1, keepdims=True)          # (bm, 1)
    first = jnp.min(jnp.where(keys == tile_key, cols_l, jnp.int32(bn)),
                    axis=1, keepdims=True)
    return tile_key, j * bn + first


def _kernel(salt_ref, c_ref, yb_ref, ya_ref, avail_ref, col_out, key_out,
            *, bm: int, bn: int):
    i = pl.program_id(0)
    j = pl.program_id(1)

    tile_key, tile_col = _tile_propose(
        c_ref[...], yb_ref[...], ya_ref[...], avail_ref[...],
        salt_ref[0, 0].astype(jnp.uint32), i, j, bm, bn,
    )

    @pl.when(j == 0)
    def _init():
        key_out[...] = jnp.full_like(key_out[...], jnp.int32(_I32_MAX))
        col_out[...] = jnp.full_like(col_out[...], -1)

    better = tile_key < key_out[...]
    key_out[...] = jnp.where(better, tile_key, key_out[...])
    col_out[...] = jnp.where(better, tile_col, col_out[...])


def _resolve_interpret(interpret: bool | None) -> bool:
    """None -> compiled on TPU (Mosaic), interpret elsewhere. The old default
    of ``interpret=True`` silently paid the emulation tax on every backend."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def slack_propose(
    c_int: jnp.ndarray,
    y_b: jnp.ndarray,
    y_a: jnp.ndarray,
    avail_a: jnp.ndarray,
    salt,
    *,
    block_m: int = 128,
    block_n: int = 128,
    interpret: bool | None = None,
):
    """Returns (best_col (m,) int32 with -1 sentinel, best_key (m,) uint32)."""
    interpret = _resolve_interpret(interpret)
    m, n = c_int.shape
    pm = (-m) % block_m
    pn = (-n) % block_n
    c_p = jnp.pad(c_int, ((0, pm), (0, pn)))
    yb_p = jnp.pad(y_b.astype(jnp.int32), (0, pm))[:, None]
    # padded columns: force non-admissible via avail = 0
    ya_p = jnp.pad(y_a.astype(jnp.int32), (0, pn))[None, :]
    av_p = jnp.pad(avail_a.astype(jnp.int32), (0, pn))[None, :]
    salt_arr = jnp.asarray(salt, jnp.int32).reshape(1, 1)
    mp, np_ = m + pm, n + pn

    grid = (mp // block_m, np_ // block_n)
    col, key = pl.pallas_call(
        functools.partial(_kernel, bm=block_m, bn=block_n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
            pl.BlockSpec((block_m, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_m, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, 1), jnp.int32),
            jax.ShapeDtypeStruct((mp, 1), jnp.int32),
        ],
        interpret=interpret,
    )(salt_arr, c_p, yb_p, ya_p, av_p)
    return col[:m, 0], _unsigned_keys(key[:m, 0])


def _kernel_batched(salt_ref, c_ref, yb_ref, ya_ref, avail_ref,
                    col_out, key_out, *, bm: int, bn: int):
    """Batched variant: grid (B, m/BM, n/BN); one instance per leading index.
    Hash keys use the within-instance (row, col) and the instance's own salt,
    so each batch slice reproduces the unbatched kernel bit for bit."""
    i = pl.program_id(1)
    j = pl.program_id(2)

    tile_key, tile_col = _tile_propose(
        c_ref[0], yb_ref[0], ya_ref[0], avail_ref[0],
        salt_ref[0, 0, 0].astype(jnp.uint32), i, j, bm, bn,
    )

    @pl.when(j == 0)
    def _init():
        key_out[...] = jnp.full_like(key_out[...], jnp.int32(_I32_MAX))
        col_out[...] = jnp.full_like(col_out[...], -1)

    better = tile_key[None] < key_out[...]
    key_out[...] = jnp.where(better, tile_key[None], key_out[...])
    col_out[...] = jnp.where(better, tile_col[None], col_out[...])


def slack_propose_batched(
    c_int: jnp.ndarray,
    y_b: jnp.ndarray,
    y_a: jnp.ndarray,
    avail_a: jnp.ndarray,
    salt: jnp.ndarray,
    *,
    block_m: int = 128,
    block_n: int = 128,
    interpret: bool | None = None,
):
    """Batched fused propose: (B, m, n) costs, per-instance duals and salts.

    Returns (best_col (B, m) int32 with -1 sentinel, best_key (B, m) uint32),
    each batch slice identical to ``slack_propose`` on that instance.
    """
    interpret = _resolve_interpret(interpret)
    b, m, n = c_int.shape
    pm = (-m) % block_m
    pn = (-n) % block_n
    c_p = jnp.pad(c_int, ((0, 0), (0, pm), (0, pn)))
    yb_p = jnp.pad(y_b.astype(jnp.int32), ((0, 0), (0, pm)))[:, :, None]
    ya_p = jnp.pad(y_a.astype(jnp.int32), ((0, 0), (0, pn)))[:, None, :]
    # padded columns: force non-admissible via avail = 0
    av_p = jnp.pad(avail_a.astype(jnp.int32), ((0, 0), (0, pn)))[:, None, :]
    salt_arr = jnp.asarray(salt, jnp.int32).reshape(b, 1, 1)
    mp, np_ = m + pm, n + pn

    grid = (b, mp // block_m, np_ // block_n)
    col, key = pl.pallas_call(
        functools.partial(_kernel_batched, bm=block_m, bn=block_n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1), lambda g, i, j: (g, 0, 0)),
            pl.BlockSpec((1, block_m, block_n), lambda g, i, j: (g, i, j)),
            pl.BlockSpec((1, block_m, 1), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, 1, block_n), lambda g, i, j: (g, 0, j)),
            pl.BlockSpec((1, 1, block_n), lambda g, i, j: (g, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_m, 1), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, block_m, 1), lambda g, i, j: (g, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, mp, 1), jnp.int32),
            jax.ShapeDtypeStruct((b, mp, 1), jnp.int32),
        ],
        interpret=interpret,
    )(salt_arr, c_p, yb_p, ya_p, av_p)
    return col[:, :m, 0], _unsigned_keys(key[:, :m, 0])
