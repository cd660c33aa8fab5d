"""Backend selection, per-backend XLA tuning flags and the persistent
compilation cache, applied BEFORE the first jax device touch.

``set_platform`` pins ``jax_platform_name`` and, for GPU, installs the
latency-hiding / async-stream XLA flags the fused phase kernels are
tuned against (the paper's GPU implementation overlaps the propose/push
sweeps with collective traffic; XLA only does the equivalent when the
latency-hiding scheduler and high-priority async streams are enabled).
``use_compile_cache`` places JAX's persistent compilation cache.
Like the mesh builders in ``launch/mesh.py``, everything here is a
FUNCTION — importing this module never touches jax backend state, and
these must run before the first computation (jax initializes its backend
once, on first use; ``jax.config.update`` after that point is silently
ignored for an already-initialized backend).

The flag set mirrors jax's own GPU performance guidance; `gpu_flags()`
exposes it separately so launchers that manage ``XLA_FLAGS`` themselves
(SLURM prologs, container entrypoints) can merge rather than overwrite.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

_GPU_XLA_FLAGS = (
    "--xla_gpu_enable_triton_softmax_fusion=true",
    "--xla_gpu_triton_gemm_any=True",
    "--xla_gpu_enable_async_collectives=true",
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)

_PLATFORMS = ("cpu", "gpu", "tpu")

# <checkout>/.jax_cache: src/repro/launch/platform.py is three levels down
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def gpu_flags() -> str:
    """The GPU XLA flag string, for launchers that merge ``XLA_FLAGS``
    themselves instead of calling :func:`set_platform`."""
    return " ".join(_GPU_XLA_FLAGS)


def set_platform(platform: str = "cpu") -> None:
    """Pin the jax backend to ``platform`` ('cpu' | 'gpu' | 'tpu') and,
    on GPU, install the latency-hiding/async-stream XLA flags.

    Call this before the first jax computation of the process; existing
    ``XLA_FLAGS`` content is preserved (our flags are appended, so an
    operator-set flag wins under XLA's last-one-wins parsing only if it
    comes later — we therefore skip any flag the environment already
    sets)."""
    if platform not in _PLATFORMS:
        raise ValueError(
            f"unknown platform {platform!r}; expected one of {_PLATFORMS}")
    jax.config.update("jax_platform_name", platform)
    if platform == "gpu":
        existing = os.environ.get("XLA_FLAGS", "")
        keep = [f for f in _GPU_XLA_FLAGS
                if f.split("=")[0] not in existing]
        os.environ["XLA_FLAGS"] = " ".join(
            ([existing] if existing else []) + keep)


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    nothing here sets another directory. Otherwise the cache lives at the
    fixed :data:`DEFAULT_CACHE_DIR` inside the checkout — a fixed path,
    because the directory is part of what a later process must find."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
