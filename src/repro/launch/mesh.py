"""Production mesh builders. FUNCTIONS (not module constants) so importing
never touches jax device state.
"""
from __future__ import annotations

import math

import jax


def _make_mesh(shape, axes, devices):
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for the production mesh, have "
            f"{len(jax.devices())} - run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=512"
        )
    return _make_mesh(shape, axes, devices)


def make_small_mesh(shape=(2, 4), axes=("data", "model")):
    """CI-scale mesh for dry-run smoke tests (8 forced host devices)."""
    n = math.prod(shape)
    return _make_mesh(shape, axes, jax.devices()[:n])


def largest_pow2_at_most(x: int) -> int:
    """Largest power of two <= max(x, 1)."""
    p = 1
    while p * 2 <= x:
        p *= 2
    return p


def make_batch_mesh(n_devices: int | None = None, axis: str = "data"):
    """1-D mesh for batch-axis sharding (core/distributed.py).

    Uses the largest power-of-two prefix of the host's devices: the
    distributed compacting driver keeps batch buckets divisible by the
    device count, and its power-of-two bucket descent only stays divisible
    when the device count is itself a power of two."""
    avail = len(jax.devices())
    n = avail if n_devices is None else min(int(n_devices), avail)
    p = largest_pow2_at_most(n)
    return _make_mesh((p,), (axis,), jax.devices()[:p])
