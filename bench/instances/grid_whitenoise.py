"""DOTmark WhiteNoise at ``traffic["resolution"]``: the class's images
(i.i.d. uniform pixels, each normalised to mass 1) on the grid's
squared-Euclidean cost. A ``"single"`` mix solves ``"pool"`` pairs one
per call; a ``"batch"`` mix solves ``"pairs"`` pairs in one call, its
lanes in an order drawn from ``rng``. Either way the pairs are the first
in DOTmark's (i < j) order."""
from __future__ import annotations

from functools import partial
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from . import Pool, data_key

MODES = ("single", "batch")


@partial(jax.jit, static_argnames=("side", "images"))
def _grid_and_images(key, side: int, images: int):
    n = side * side
    idx = jnp.arange(n, dtype=jnp.int32)
    p = jnp.stack([idx // side, idx % side], 1).astype(jnp.float32) \
        / max(side - 1, 1)
    c = jnp.sum((p[:, None, :] - p[None, :, :]) ** 2, -1)
    img = jax.random.uniform(key, (images, n), jnp.float32)
    return c, img / jnp.sum(img, axis=1, keepdims=True)


def dotmark_pairs(images: int) -> List[tuple]:
    """DOTmark's pairs of one class and resolution, (i, j) with i < j."""
    return [(i, j) for i in range(images) for j in range(i + 1, images)]


def make(config: dict, traffic: dict, rng) -> Pool:
    side = int(traffic["resolution"])
    c, img = _grid_and_images(data_key(config, side), side,
                              int(config["images_per_resolution"]))
    img_h = np.asarray(img)
    pairs = dotmark_pairs(int(config["images_per_resolution"]))
    if traffic["mode"] == "single":
        pairs = pairs[:int(traffic["pool"])]
        cb = c[None]
        items = [{"c": cb, "nu": img[i][None], "mu": img[j][None]}
                 for i, j in pairs]
        host = [[{"side": side, "nu": img_h[i], "mu": img_h[j]}]
                for i, j in pairs]
        return Pool(items=items, host=host)
    pairs = pairs[:int(traffic["pairs"])]
    pairs = [pairs[k] for k in rng.permutation(len(pairs))]
    a = np.asarray([i for i, _ in pairs])
    b = np.asarray([j for _, j in pairs])
    item = {"c": jnp.broadcast_to(c, (len(pairs),) + c.shape),
            "nu": img[a], "mu": img[b]}
    host = [[{"side": side, "nu": img_h[i], "mu": img_h[j]}
             for i, j in pairs]]
    return Pool(items=[item], host=host)
