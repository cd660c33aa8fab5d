"""Pallas TPU kernels for pairwise cost matrices (the paper's experiment
inputs: Euclidean on 2-D points, L1 on normalized images).

sqeuclidean/euclidean use the MXU through the Gram identity
``|x|^2 + |y|^2 - 2 x.y^T`` - the kernel is one (BM, D) x (D, BN) matmul per
tile plus a VPU epilogue. L1 has no matmul form; the kernel streams the
feature axis in chunks of K to bound the (BM, BN, K) broadcast in VMEM.

``cost_matrix_batched`` adds a leading batch axis to the grid — grid
(B, m/BM, n/BN), one instance per leading index, mirroring
``slack_propose_batched``'s layout — so a whole shape bucket of point
clouds becomes ONE kernel launch. Both variants share the same tile bodies,
so each batch slice is bit-identical to the unbatched kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .slack_propose import _resolve_interpret


def _sqeuclid_tile(x, y, euclid: bool):
    """Shared (BM, D) x (BN, D) -> (BM, BN) tile body."""
    x2 = jnp.sum(x * x, axis=1, keepdims=True)
    y2 = jnp.sum(y * y, axis=1, keepdims=True)
    # full f32 contraction: a single bf16 MXU pass would cost ~1e-2 on
    # unit-scale points, more than a cost-rounding step at small eps
    g = jax.lax.dot_general(
        x, y, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    d = jnp.maximum(x2 + y2.T - 2.0 * g, 0.0)
    return jnp.sqrt(d + 1e-30) if euclid else d


def _sqeuclid_kernel(x_ref, y_ref, o_ref, *, euclid: bool):
    o_ref[...] = _sqeuclid_tile(x_ref[...], y_ref[...], euclid)


def _sqeuclid_kernel_batched(x_ref, y_ref, o_ref, *, euclid: bool):
    o_ref[0] = _sqeuclid_tile(x_ref[0], y_ref[0], euclid)


def _l1_tile(x_ref, y_ref, k: int, d: int, bm: int, bn: int, batched: bool):
    """Shared L1 tile body: stream the feature axis in chunks of k."""
    steps = d // k

    def load(ref, s):
        if batched:
            return ref[0, :, pl.dslice(s * k, k)]
        return ref[:, pl.dslice(s * k, k)]

    def body(s, acc):
        xc = load(x_ref, s)
        yc = load(y_ref, s)
        return acc + jnp.sum(
            jnp.abs(xc[:, None, :] - yc[None, :, :]), axis=-1
        )

    return jax.lax.fori_loop(
        0, steps, body, jnp.zeros((bm, bn), jnp.float32)
    )


def _l1_kernel(x_ref, y_ref, o_ref, *, k: int, d: int):
    bm, bn = x_ref.shape[0], y_ref.shape[0]
    o_ref[...] = _l1_tile(x_ref, y_ref, k, d, bm, bn, batched=False)


def _l1_kernel_batched(x_ref, y_ref, o_ref, *, k: int, d: int):
    bm, bn = x_ref.shape[1], y_ref.shape[1]
    o_ref[0] = _l1_tile(x_ref, y_ref, k, d, bm, bn, batched=True)


def cost_matrix(
    x: jnp.ndarray,
    y: jnp.ndarray,
    metric: str = "sqeuclidean",
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 32,
    interpret: bool | None = None,
):
    m, d = x.shape
    n, d2 = y.shape
    assert d == d2
    pm, pn = (-m) % block_m, (-n) % block_n
    pk = (-d) % block_k if metric == "l1" else 0
    x_p = jnp.pad(x.astype(jnp.float32), ((0, pm), (0, pk)))
    y_p = jnp.pad(y.astype(jnp.float32), ((0, pn), (0, pk)))
    mp, np_, dp = m + pm, n + pn, d + pk
    grid = (mp // block_m, np_ // block_n)

    if metric in ("sqeuclidean", "euclidean"):
        kern = functools.partial(_sqeuclid_kernel, euclid=metric == "euclidean")
    elif metric == "l1":
        kern = functools.partial(_l1_kernel, k=block_k, d=dp)
    else:
        raise ValueError(metric)

    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, dp), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, dp), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=_resolve_interpret(interpret),
    )(x_p, y_p)
    return out[:m, :n]


def cost_matrix_batched(
    x: jnp.ndarray,
    y: jnp.ndarray,
    metric: str = "sqeuclidean",
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 32,
    interpret: bool | None = None,
):
    """Batched pairwise costs: (B, m, d) x (B, n, d) -> (B, m, n).

    One kernel launch for the whole batch, grid (B, m/BM, n/BN); each batch
    slice is bit-identical to ``cost_matrix`` on that instance (identical
    tile bodies, identical padded-tile handling)."""
    b, m, d = x.shape
    b2, n, d2 = y.shape
    assert b == b2 and d == d2
    pm, pn = (-m) % block_m, (-n) % block_n
    pk = (-d) % block_k if metric == "l1" else 0
    x_p = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, pm), (0, pk)))
    y_p = jnp.pad(y.astype(jnp.float32), ((0, 0), (0, pn), (0, pk)))
    mp, np_, dp = m + pm, n + pn, d + pk
    grid = (b, mp // block_m, np_ // block_n)

    if metric in ("sqeuclidean", "euclidean"):
        kern = functools.partial(_sqeuclid_kernel_batched,
                                 euclid=metric == "euclidean")
    elif metric == "l1":
        kern = functools.partial(_l1_kernel_batched, k=block_k, d=dp)
    else:
        raise ValueError(metric)

    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_m, dp), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, block_n, dp), lambda g, i, j: (g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_m, block_n),
                               lambda g, i, j: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, mp, np_), jnp.float32),
        interpret=_resolve_interpret(interpret),
    )(x_p, y_p)
    return out[:, :m, :n]
