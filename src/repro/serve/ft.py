"""Fault-tolerance helpers shared by the serving layers.

Three concerns live here so ``OTService`` and ``AsyncOTScheduler`` agree
on them exactly:

  * request validation (:func:`require_mass_pair` — the one home of the
    "provide both nu and mu" rule, naming the failing request/tenant);
  * failure classification (:func:`is_transient` vs :func:`is_poison`):
    a transient infrastructure failure (device OOM, mesh collective
    error) is worth retrying on a safer rung, while poison (a checkify
    ``JaxRuntimeError`` from NaN inputs, a corrupted-state invariant) is
    a property of the DATA — retrying reproduces it, so the right move
    is bisection and quarantine;
  * the degradation ladder (:func:`degradation_ladder` +
    :func:`run_with_recovery`): transient failures retry with
    exponential backoff down ``mesh -> compact single-device -> host
    CPU``. The last rung pins the compacting driver to the host CPU
    device — the safe-harbor equivalent of "lockstep on CPU" that still
    honors the per-request eps arrays and deadlines serving buckets
    carry (the lockstep driver can express neither).
"""
from __future__ import annotations

import time
from typing import Any, Callable, List, Optional, Tuple

import jax
from jax.experimental import checkify

from ..core.validate import RequestRejected  # noqa: F401  (re-export: the
#   serving layers raise it for both admission and dispatch-time poison)

__all__ = [
    "RequestRejected",
    "TransientDispatchError",
    "require_mass_pair",
    "is_transient",
    "is_poison",
    "degradation_ladder",
    "run_with_recovery",
]


class TransientDispatchError(RuntimeError):
    """A dispatch failure worth retrying: the inputs are fine, the
    attempt was not (device OOM, collective timeout, injected chaos)."""


def require_mass_pair(nu, mu, *, who: str = "request") -> bool:
    """The one home of the nu/mu pairing rule: both present (general OT)
    or both absent (assignment distance). Returns ``has_mass``; raises a
    ``ValueError`` that names the offending request/tenant."""
    if (nu is None) != (mu is None):
        supplied = "nu" if nu is not None else "mu"
        raise ValueError(
            f"provide both nu and mu (general OT) or neither (assignment "
            f"distance): {who} supplied only {supplied}")
    return nu is not None


def is_transient(exc: BaseException) -> bool:
    """Worth retrying? Injected :class:`TransientDispatchError`, plus
    device-runtime failures (``jax.errors.JaxRuntimeError``: OOM,
    collective errors, backend faults) — those are attempt properties, not
    data properties, and a smaller/safer rung may succeed. Poison (see
    :func:`is_poison`) is never transient."""
    if isinstance(exc, TransientDispatchError):
        return True
    return isinstance(exc, jax.errors.JaxRuntimeError) and not is_poison(exc)


def is_poison(exc: BaseException) -> bool:
    """A data-dependent failure: retrying the same lanes reproduces it,
    so the caller should bisect and quarantine instead. Matches the
    checkify sanitizer's ``JaxRuntimeError`` (REPRO_DEBUG_CHECKS=1 NaN /
    invariant trips), plain ``FloatingPointError``, and anything tagged
    ``poisoned_instance`` (the fault-injection harness)."""
    if isinstance(exc, FloatingPointError):
        return True
    if getattr(exc, "poisoned_instance", False):
        return True
    return isinstance(exc, checkify.JaxRuntimeError)


def degradation_ladder(policy) -> List[Tuple[str, Any, Any]]:
    """``[(level_name, policy, pinned_device), ...]`` from the configured
    policy down to the host-CPU safe harbor.

    Level 0 is the caller's policy verbatim. Each later rung strips one
    failure surface: ``compact`` drops the mesh (no collectives, one
    device), ``cpu`` additionally pins dispatch to the host CPU device
    (survives an accelerator wedged by OOM). Rungs equal to the
    configured policy are deduplicated, so a compact-policy scheduler
    gets a 2-rung ladder.
    """
    from ..core.api import DispatchPolicy

    mode = policy.resolved_mode()
    ladder: List[Tuple[str, Any, Any]] = [(mode, policy, None)]
    compact = DispatchPolicy(
        mode="compact", chunk=policy.chunk, buckets=policy.buckets,
        guaranteed=policy.guaranteed)
    if mode != "compact":
        ladder.append(("compact", compact, None))
    cpus = jax.devices("cpu")
    if cpus:
        cpu0 = cpus[0]
        if jax.default_backend() != "cpu" or mode == "mesh":
            ladder.append(("cpu", compact, cpu0))
    return ladder


def run_with_recovery(
    attempt: Callable[[str, Any, Any], Any],
    ladder: List[Tuple[str, Any, Any]],
    *,
    retries_per_level: int = 2,
    backoff_s: float = 0.05,
    sleep: Callable[[float], None] = time.sleep,
    transient: Callable[[BaseException], bool] = is_transient,
) -> Tuple[Any, int, int]:
    """Run ``attempt(level_name, policy, device)`` down the ladder.

    Transient failures retry ``retries_per_level`` times per rung with
    exponential backoff (``backoff_s * 2**attempt_on_level``), then fall
    to the next rung. Non-transient failures (poison, programming errors)
    propagate immediately — retrying data-dependent failures only burns
    budget reproducing them. Returns ``(result, level_index,
    total_attempts)``; exhausting the ladder re-raises the last error.
    """
    last: Optional[BaseException] = None
    total = 0
    for level, (name, pol, dev) in enumerate(ladder):
        for a in range(max(1, retries_per_level)):
            total += 1
            try:
                return attempt(name, pol, dev), level, total
            except Exception as e:
                if not transient(e):
                    raise
                last = e
                if backoff_s > 0:
                    sleep(backoff_s * (2 ** a))
    assert last is not None
    raise last
