"""The general traffic generator: instances of a configuration, made on the
device from the configuration's data seed, in the form a solve call takes.

Each instance family (a configuration's ``"instance"`` key) is a module
of its own, ``bench/instances/<family>.py``, found by that name. It gives
``MODES``, the traffic modes it reads, and ``make(config, traffic, rng)``,
which returns a :class:`Pool`. A traffic mix (``bench/traffic/<name>.json``)
says how many instances, at which size, and whether they arrive one per
call (``"single"``) or all in one call (``"batch"``).

Every seed of a run solves the same set of instances: ``--seed`` orders
them and draws the answers that are checked, so the work of a run does
not depend on its seed. The push-relabel trajectory, and with it the
work, changes with the instance's data and even with the order of its
rows, so instances drawn per seed would make the seed change the time.

The costs are built in plain ``jax.numpy`` (not with the program's cost
builders), and the host copies of the points and masses that the
reference (``bench/reference``) rebuilds the costs from in float64 come
back beside them.
"""
from __future__ import annotations

import importlib
from dataclasses import dataclass
from typing import Any, Dict, List

import jax


@dataclass
class Pool:
    """What a cell solves: ``items`` are the solve-call inputs (dicts of
    (B, ...) device arrays); ``host`` the float64 reference's view of each
    item (one dict per lane)."""
    items: List[Dict[str, Any]]
    host: List[List[Dict[str, Any]]]


def data_key(config: dict, *fold) -> jax.Array:
    """The configuration's fixed data key, folded with ``fold``."""
    key = jax.random.key(int(config["assumed"]["data_seed"]) % (1 << 31))
    for f in fold:
        key = jax.random.fold_in(key, f)
    return key


def family(name: str):
    """``bench/instances/<name>.py``."""
    return importlib.import_module(f"{__name__}.{name}")


def make_pool(config: dict, traffic: dict, rng) -> Pool:
    """The cell's instances, made on the device and waited for; ``rng``
    (from the run's seed) orders the lanes of a batch."""
    fam = family(config["instance"])
    if traffic["mode"] not in fam.MODES:
        raise ValueError(f"instance family {config['instance']!r} has no "
                         f"traffic mode {traffic['mode']!r}")
    pool = fam.make(config, traffic, rng)
    jax.block_until_ready(pool.items)
    return pool
