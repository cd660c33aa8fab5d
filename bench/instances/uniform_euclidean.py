"""The paper's synthetic assignment input (arXiv:2203.03732, section 5):
two sets of n uniform points in the unit square, Euclidean cost divided
by its maximum. A ``"single"`` mix solves ``"pool"`` of them, one per
call."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import Pool, data_key

MODES = ("single",)


@partial(jax.jit, static_argnames=("count", "n", "dim"))
def _points_and_costs(key, count: int, n: int, dim: int):
    pts = jax.random.uniform(key, (count, 2, n, dim), jnp.float32)
    x, y = pts[:, 0], pts[:, 1]
    c = jnp.sqrt(jnp.sum((x[:, :, None, :] - y[:, None, :, :]) ** 2, -1))
    return c / jnp.max(c, axis=(1, 2), keepdims=True), x, y


def make(config: dict, traffic: dict, rng) -> Pool:
    count = int(traffic["pool"])
    c, x, y = _points_and_costs(data_key(config), count, int(config["n"]),
                                int(config["dim"]))
    x, y = np.asarray(x), np.asarray(y)
    items = [{"c": c[i:i + 1]} for i in range(count)]
    host = [[{"x": x[i], "y": y[i]}] for i in range(count)]
    return Pool(items=items, host=host)
