"""Hierarchical spans over the monotonic clock, emitted as events.

A ``Span`` is one timed region (``t_start``/``t_end`` from
``metrics.now``) with a name, a trace id (per-request or per-bucket),
its own span id, and an optional parent span id — enough to rebuild the
tree submit → admission → collate → bucket dispatch → per-chunk solve →
artifact fetch from a flat event stream.  Spans are emitted ONCE, on
``end()``, as a single ``"span"`` event carrying both timestamps; there
is no partial state to lock.

``Tracer`` is the handle threaded through the serving stack: it holds
the registry (for sink fan-out), default trace/parent ids, and default
attributes.  ``bind()`` derives a child tracer with different defaults —
this is how the chunked drivers' per-chunk events get parented under the
dispatch's solve span without the drivers knowing about scheduling.

Spans also land on the profiler's host timeline: ``annotation(name)``
enters ``jax.profiler.TraceAnnotation("repro." + name)``, on the clock of
the device trace, and ``Tracer.span`` enters it around its body.
``region(obs, name)`` is the one helper the solve path marks its layers
with: nothing for ``obs=None``, a ``Span`` (with its annotation) for a
``Tracer``, and the annotation alone for any other event emitter.

Thread-safety: span ids come from ``itertools.count`` (atomic in
CPython); a ``Span`` is only ever mutated by the thread that ends it;
``Tracer`` itself is immutable after construction.  Scan-exempt for
those reasons.
"""
from __future__ import annotations

import itertools
from contextlib import contextmanager, nullcontext
from typing import Any, ContextManager, Dict, Iterator, Optional

from .metrics import MetricsRegistry, now

_ids = itertools.count(1)


def new_id(prefix: str) -> str:
    """A process-unique id, e.g. ``new_id('req') -> 'req-17'``."""
    return f"{prefix}-{next(_ids)}"


class Span:
    """One timed region.  Emitted as a ``"span"`` event on ``end()``."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "t_start",
                 "t_end", "attrs", "_tracer")

    def __init__(self, name: str, trace_id: str, span_id: int,
                 parent_id: Optional[int], attrs: Dict[str, Any],
                 tracer: "Tracer") -> None:
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.t_start = now()
        self.t_end: Optional[float] = None
        self.attrs = attrs
        self._tracer = tracer

    def end(self, **attrs: Any) -> None:
        if self.t_end is not None:  # idempotent: first end wins
            return
        self.t_end = now()
        payload: Dict[str, Any] = {
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "t_start": self.t_start,
            "t_end": self.t_end,
            "dur_s": self.t_end - self.t_start,
        }
        payload.update(self.attrs)
        payload.update(attrs)
        self._tracer.registry.emit("span", payload)


class Tracer:
    """Factory for spans and structured events over one registry."""

    __slots__ = ("registry", "trace_id", "parent_id", "attrs")

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 trace_id: Optional[str] = None,
                 parent_id: Optional[int] = None,
                 **attrs: Any) -> None:
        self.registry = registry if registry is not None else MetricsRegistry()
        self.trace_id = trace_id
        self.parent_id = parent_id
        self.attrs = attrs

    def bind(self, trace_id: Optional[str] = None,
             parent: Optional[int] = None, **attrs: Any) -> "Tracer":
        """Derive a tracer with new default trace/parent ids and attrs."""
        merged = dict(self.attrs)
        merged.update(attrs)
        return Tracer(self.registry,
                      trace_id=trace_id if trace_id is not None
                      else self.trace_id,
                      parent_id=parent if parent is not None
                      else self.parent_id,
                      **merged)

    def start(self, name: str, trace_id: Optional[str] = None,
              parent: Optional[int] = None, **attrs: Any) -> Span:
        """Begin a span; the caller must ``end()`` it (possibly on
        another thread — spans routinely cross the submit/dispatch
        thread boundary)."""
        merged = dict(self.attrs)
        merged.update(attrs)
        tid = trace_id if trace_id is not None else self.trace_id
        if tid is None:
            tid = new_id("trace")
        pid = parent if parent is not None else self.parent_id
        return Span(name, tid, next(_ids), pid, merged, self)

    @contextmanager
    def span(self, name: str, trace_id: Optional[str] = None,
             parent: Optional[int] = None, **attrs: Any) -> Iterator[Span]:
        """A span around the body, also marked on the profiler's host
        timeline (:func:`annotation`)."""
        s = self.start(name, trace_id=trace_id, parent=parent, **attrs)
        try:
            with annotation(name):
                yield s
        except BaseException as e:
            s.end(error=type(e).__name__)
            raise
        else:
            s.end()

    def event(self, kind: str, **attrs: Any) -> None:
        """Emit a point-in-time structured event."""
        payload: Dict[str, Any] = {"t": now()}
        if self.trace_id is not None:
            payload["trace_id"] = self.trace_id
        if self.parent_id is not None:
            payload["parent_id"] = self.parent_id
        payload.update(self.attrs)
        payload.update(attrs)
        self.registry.emit(kind, payload)


@contextmanager
def annotation(name: str) -> Iterator[None]:
    """The body as a ``repro.<name>`` span on the profiler's host timeline
    (``jax.profiler.TraceAnnotation``), on the same clock as the program
    launches and device executions of a captured trace."""
    import jax  # lazily: repro.obs imports only the stdlib

    with jax.profiler.TraceAnnotation("repro." + name):
        yield


_NO_REGION = nullcontext()


def region(obs, name: str, *, record: bool = True) -> ContextManager[Any]:
    """Mark one layer of a solve call for ``obs``, the solve path's
    optional event emitter: nothing when ``obs`` is None; a ``Span`` (with
    its annotation) when it is a :class:`Tracer` and ``record`` is set;
    the :func:`annotation` alone otherwise. ``record=False`` is for
    regions the caller already reports to the sinks as an event of its
    own, as the drivers do with each ``"chunk"``."""
    if obs is None:
        return _NO_REGION
    if record and isinstance(obs, Tracer):
        return obs.span(name)
    return annotation(name)


def span_tree(events, trace_id: Optional[str] = None) -> str:
    """Render ``"span"`` events (dicts) as an indented tree — demo/debug
    helper used by quickstart section 14."""
    spans = [e for e in events
             if e.get("name") is not None and "span_id" in e
             and (trace_id is None or e.get("trace_id") == trace_id)]
    by_parent: Dict[Optional[int], list] = {}
    ids = {s["span_id"] for s in spans}
    for s in spans:
        p = s.get("parent_id")
        by_parent.setdefault(p if p in ids else None, []).append(s)
    lines: list = []

    def walk(parent: Optional[int], depth: int) -> None:
        for s in sorted(by_parent.get(parent, []),
                        key=lambda x: x["t_start"]):
            lines.append("  " * depth
                         + f"{s['name']} [{s['trace_id']}] "
                         f"{1e3 * s['dur_s']:.2f} ms")
            walk(s["span_id"], depth + 1)

    walk(None, 0)
    return "\n".join(lines)
