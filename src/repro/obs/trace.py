"""Span tracer: per-request / per-instance timelines on both planes.

The model is deliberately tiny — five primitives:

  - ``begin(track, name, t)`` / ``end(track, name, t)`` — an open span,
    keyed by ``(track, name)``; used when the end time is only known
    later (the sim plane's decode steps).
  - ``span(track, name, start, end)`` — a complete span in one call;
    used when both edges are known at record time (adapter loads).
  - ``instant(track, name, t)`` — a point event (KV page allocation,
    store prefetch kickoff, autoscaler actions).
  - ``counter(track, name, t, value)`` — a sampled time series (queue
    depth per round, decode rows per engine step).
  - ``scope(name, **args)`` — a context manager for a host span whose
    two edges the tracer takes itself, on the wall clock; nested scopes
    on one thread record their parent.

Each plane has ONE clock, and every timestamp it records is on it:

  - the sim plane stamps its virtual time (the event heap's clock);
  - the cluster plane stamps ``repro.obs.clock.wall_time()``, the
    ``time.perf_counter`` seconds that ``scope`` takes itself, so its
    spans line up with each other, with a caller's own perf_counter
    readings and, through the injected profiler annotation, with the
    device trace (``scope`` enters ``jax.profiler.TraceAnnotation`` when
    the serving layer supplies it).

Exporters (``repro.obs.export``) turn the recorded timeline into
Chrome/Perfetto trace JSON or JSONL.

``NULL_TRACER`` is the default everywhere: all methods are no-ops that
allocate nothing (``scope`` returns one shared no-op context), and
``enabled`` is False so hot paths can skip even building the call
arguments.
"""
from __future__ import annotations

import dataclasses
import gc
import threading
import weakref
from typing import Callable, Dict, List, Optional, Tuple

from repro.obs.clock import wall_time

#: the track every ``scope`` span is recorded on
SCOPE_TRACK = "serve"


@dataclasses.dataclass
class Span:
    """One recorded interval (or point, when ``end == start``)."""
    track: str
    name: str
    start: float
    end: float
    args: Optional[Dict[str, object]] = None
    # the enclosing ``scope`` span on the same thread (scope spans only)
    parent: Optional["Span"] = dataclasses.field(default=None, repr=False,
                                                 compare=False)

    @property
    def duration(self) -> float:
        return self.end - self.start


class _NoScope:
    """The shared do-nothing context ``Tracer.scope`` returns."""
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


#: the no-op context ``Tracer.scope`` returns; a caller whose scope
#: arguments cost something to build uses it when tracing is off
NO_SCOPE = _NoScope()


class Tracer:
    """The tracing protocol both planes program against. The base class
    IS the null implementation contract: subclasses that record set
    ``enabled = True``; callers guard expensive argument construction on
    it. Timestamps are on the producing plane's clock (module doc)."""
    enabled: bool = False

    def scope(self, name: str, **args):
        """Context manager: a host span from entry to exit, both edges
        taken by the tracer on the wall clock."""
        return NO_SCOPE

    def begin(self, track: str, name: str, t: float, **args) -> None:
        """Open a span keyed by ``(track, name)``."""

    def end(self, track: str, name: str, t: float, **args) -> None:
        """Close the matching open span (no-op if none is open)."""

    def span(self, track: str, name: str, start: float, end: float,
             **args) -> None:
        """Record a complete span in one call."""

    def instant(self, track: str, name: str, t: float, **args) -> None:
        """Record a point event."""

    def counter(self, track: str, name: str, t: float,
                value: float) -> None:
        """Record one sample of a time series."""

    def finish(self, t: float) -> None:
        """Close any still-open spans at time ``t``."""

    def close(self) -> None:
        """Release what the tracer holds outside itself."""


class NullTracer(Tracer):
    """Zero-cost tracer: records nothing, allocates nothing. The default
    on every plane (``ServeConfig.trace=False``)."""
    __slots__ = ()


NULL_TRACER = NullTracer()


class _Scope:
    """One ``TimelineTracer.scope`` span in flight."""
    __slots__ = ("_tracer", "_span", "_note")

    def __init__(self, tracer: "TimelineTracer", name: str,
                 args: Optional[Dict]):
        self._tracer = tracer
        self._span = Span(SCOPE_TRACK, name, 0.0, 0.0, args)
        self._note = None

    def __enter__(self) -> Span:
        tr, span = self._tracer, self._span
        stack = tr._stack()
        span.parent = stack[-1] if stack else None
        stack.append(span)
        if tr._annotate is not None:
            self._note = tr._annotate(span.name, **(span.args or {}))
            self._note.__enter__()
        span.start = wall_time()
        return span

    def __exit__(self, *exc):
        tr, span = self._tracer, self._span
        span.end = wall_time()
        if self._note is not None:
            self._note.__exit__(*exc)
        tr._stack().pop()
        tr.spans.append(span)
        return False


class TimelineTracer(Tracer):
    """Recording tracer: appends every primitive to in-memory lists that
    the exporters read. Both planes drive it from their main loop;
    ``scope`` keeps one nesting stack per thread.

    ``annotate`` is a profiler annotation factory (the serving layer
    passes ``jax.profiler.TraceAnnotation``): every ``scope`` span also
    enters one, so it lands on the profile's host plane, on the device
    trace's clock. ``gc_spans`` records each Python garbage collection
    as a ``serve.gc`` scope span (generation, objects collected) through
    ``gc.callbacks``, until ``close()``."""
    enabled = True

    def __init__(self, annotate: Optional[Callable] = None,
                 gc_spans: bool = False) -> None:
        self.spans: List[Span] = []
        self.instants: List[Span] = []
        self.counters: List[Tuple[str, str, float, float]] = []
        self._open: Dict[Tuple[str, str], Tuple[float, Optional[Dict]]] = {}
        self._annotate = annotate
        self._local = threading.local()
        self._gc_cb = None
        if gc_spans:
            self._gc_cb = _gc_callback(weakref.ref(self))
            gc.callbacks.append(self._gc_cb)

    def _stack(self) -> List[Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def scope(self, name: str, **args) -> _Scope:
        return _Scope(self, name, args or None)

    def close(self) -> None:
        """Stop recording garbage collections (idempotent)."""
        if self._gc_cb is not None:
            gc.callbacks.remove(self._gc_cb)
            self._gc_cb = None

    def begin(self, track: str, name: str, t: float, **args) -> None:
        self._open[(track, name)] = (float(t), args or None)

    def end(self, track: str, name: str, t: float, **args) -> None:
        opened = self._open.pop((track, name), None)
        if opened is None:
            return                      # unmatched end: drop, don't invent
        start, a = opened
        if args:
            a = {**(a or {}), **args}
        self.spans.append(Span(track, name, start, float(t), a))

    def span(self, track: str, name: str, start: float, end: float,
             **args) -> None:
        self.spans.append(Span(track, name, float(start), float(end),
                               args or None))

    def instant(self, track: str, name: str, t: float, **args) -> None:
        self.instants.append(Span(track, name, float(t), float(t),
                                  args or None))

    def counter(self, track: str, name: str, t: float,
                value: float) -> None:
        self.counters.append((track, name, float(t), float(value)))

    def finish(self, t: float) -> None:
        """Close every open span at ``max(t, start)`` — called once at
        export/drain time so a trace never loses in-flight work."""
        for (track, name), (start, a) in sorted(self._open.items()):
            self.spans.append(Span(track, name, start, max(float(t), start),
                                   a))
        self._open.clear()

    # --------------------------- inspection --------------------------- #
    def tracks(self) -> List[str]:
        """Track names in first-appearance order (stable export layout)."""
        seen: Dict[str, None] = {}
        for s in self.spans:
            seen.setdefault(s.track, None)
        for s in self.instants:
            seen.setdefault(s.track, None)
        for track, _, _, _ in self.counters:
            seen.setdefault(track, None)
        return list(seen)

    def spans_for(self, track: str) -> List[Span]:
        """Spans on one track, sorted by (start, end)."""
        return sorted((s for s in self.spans if s.track == track),
                      key=lambda s: (s.start, s.end))

    def children(self, span: Span) -> List[Span]:
        """The scope spans whose parent is ``span``, by start."""
        return sorted((s for s in self.spans if s.parent is span),
                      key=lambda s: s.start)


def _gc_callback(ref):
    """A ``gc.callbacks`` entry recording collections on the tracer
    ``ref`` points to, as ``serve.gc`` scope spans; it holds the tracer
    weakly and does nothing once the tracer is gone."""
    open_: Dict[int, _Scope] = {}

    def on_gc(phase: str, info: Dict) -> None:
        tracer = ref()
        if tracer is None:
            return
        key = threading.get_ident()
        if phase == "start":
            sc = _Scope(tracer, "serve.gc",
                        {"generation": info["generation"]})
            open_[key] = sc
            sc.__enter__()
        else:
            sc = open_.pop(key, None)
            if sc is not None:
                sc._span.args["collected"] = info["collected"]
                sc.__exit__(None, None, None)
    return on_gc
