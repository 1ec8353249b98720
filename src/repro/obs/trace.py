"""Host-side span tracing: structured timing for everything OUTSIDE the jit.

The device half of observability (``obs.telemetry``) rides the while_loop;
this module covers the host half: the program's entry points (the
process-wide :data:`PROGRAM` tracer, always on), and the service's
pump/admit/readback cycle and per-ticket lifecycles (a tracer the caller
hands the service).

Every ``span()`` also opens a ``jax.profiler.TraceAnnotation`` carrying
the span's ``span_id`` (and ``parent_id``) as metadata, so while a
profiler trace is being captured each span lands in the same
``.xplane.pb`` as the device operations, on the same clock; the
JSON-lines export (perf-counter clock) joins that trace on ``span_id``.
With no profiler running the annotation is a no-op.  Intervals logged
after the fact with ``record()`` cannot be annotated and live in the
export only.

Spans are plain records with a pinned schema (:data:`SPAN_KEYS`), kept in
a bounded ring: the oldest drop once ``capacity`` is reached, while the
per-name totals (count, seconds) and the per-site counters keep counting
(see docs/OBSERVABILITY.md).

A :class:`NullTracer` stands in where tracing is off: every call is a
no-op, so instrumented hot paths pay one attribute lookup, not an if-tree.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import io
import itertools
import json
import threading
import time

from jax.profiler import TraceAnnotation

#: Pinned span schema: every exported JSON line has exactly these keys.
SPAN_KEYS = frozenset(
    {"name", "span_id", "parent_id", "t_start", "t_end", "dur_ms", "thread", "attrs"}
)

#: Schema version stamped into exports (bump on any SPAN_KEYS change).
SPAN_SCHEMA_VERSION = 1

#: Spans a tracer's ring holds by default.
DEFAULT_SPAN_CAPACITY = 1 << 14


@dataclasses.dataclass(slots=True)
class Span:
    """One completed span: a named ``[t_start, t_end]`` interval + attrs."""

    name: str
    span_id: int
    parent_id: int | None
    t_start: float
    t_end: float
    thread: str
    attrs: dict

    def to_dict(self) -> dict:
        """The pinned-schema dict this span exports as (one JSON line)."""
        return {
            "name": self.name,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "t_start": self.t_start,
            "t_end": self.t_end,
            "dur_ms": (self.t_end - self.t_start) * 1e3,
            "thread": self.thread,
            "attrs": self.attrs,
        }


class Tracer:
    """Collects spans in a bounded ring; thread-safe; nesting per thread.

    ``span(name, **attrs)`` is the context-manager form: it times the
    block, parents the spans opened inside it, and yields its ``attrs``
    dict so the block can add what it learns on the way (a cache hit, the
    bytes it moved); ``traced(name)`` makes a function's every call one
    span, and ``note(**attrs)`` adds to the innermost open span from
    inside it.  ``record(name, t_start, t_end, **attrs)`` logs an
    interval whose endpoints were captured elsewhere -- the service uses it
    to emit one ``ticket`` span per request at retirement from the
    timestamps the ticket already carries.  ``add(counter, site, value)``
    counts at the same boundaries (for example bytes moved, by call site).

    The ring keeps the newest ``capacity`` spans; :meth:`totals` and
    :meth:`counters` count every span and every addition since the last
    :meth:`clear`.
    """

    def __init__(self, capacity: int = DEFAULT_SPAN_CAPACITY, clock=time.perf_counter):
        self.capacity = int(capacity)
        self._spans: collections.deque[Span] = collections.deque(maxlen=self.capacity)
        self._totals: dict[str, list] = {}
        self._counters: dict[str, dict] = {}
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._clock = clock

    def _stack(self):
        if not hasattr(self._local, "stack"):
            self._local.stack = []
        return self._local.stack

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """Time a ``with`` block as one span (nested spans get parented);
        yields the span's ``attrs`` dict, which the block may extend."""
        sid = next(self._ids)
        stack = self._stack()
        parent = stack[-1][0] if stack else None
        stack.append((sid, attrs))
        meta = {"span_id": sid} if parent is None else {"span_id": sid, "parent_id": parent}
        t0 = self._clock()
        try:
            with TraceAnnotation(name, **meta):
                yield attrs
        finally:
            t1 = self._clock()
            stack.pop()
            self._append(Span(name, sid, parent, t0, t1, _thread_name(), attrs))

    def traced(self, name: str):
        """Decorator: every call of the function is one ``name`` span."""

        def wrap(fn):
            @functools.wraps(fn)
            def call(*args, **kwargs):
                with self.span(name):
                    return fn(*args, **kwargs)

            return call

        return wrap

    def note(self, **attrs) -> None:
        """Add ``attrs`` to the innermost span this thread has open (none
        open: a no-op)."""
        stack = self._stack()
        if stack:
            stack[-1][1].update(attrs)

    def record(
        self, name: str, t_start: float, t_end: float, parent_id=None, **attrs
    ) -> int:
        """Log a span from externally captured endpoints; returns its id."""
        sid = next(self._ids)
        if parent_id is None:
            stack = self._stack()
            parent_id = stack[-1][0] if stack else None
        self._append(Span(name, sid, parent_id, t_start, t_end, _thread_name(), attrs))
        return sid

    def add(self, counter: str, site: str, value) -> None:
        """Add ``value`` to ``counter`` at ``site`` (kept past the ring)."""
        with self._lock:
            sites = self._counters.setdefault(counter, {})
            sites[site] = sites.get(site, 0) + value

    def _append(self, span: Span):
        with self._lock:
            self._spans.append(span)
            total = self._totals.setdefault(span.name, [0, 0.0])
            total[0] += 1
            total[1] += span.t_end - span.t_start

    def spans(self) -> list[Span]:
        """Snapshot of the spans the ring holds, oldest first (a copy)."""
        with self._lock:
            return list(self._spans)

    def totals(self) -> dict:
        """``{name: {"count", "seconds"}}`` over every span since ``clear``."""
        with self._lock:
            return {k: {"count": c, "seconds": s} for k, (c, s) in self._totals.items()}

    def counters(self) -> dict:
        """``{counter: {site: total}}`` over every ``add`` since ``clear``."""
        with self._lock:
            return {k: dict(v) for k, v in self._counters.items()}

    def clear(self):
        """Drop every span, total and counter (export first if you want them)."""
        with self._lock:
            self._spans.clear()
            self._totals.clear()
            self._counters.clear()

    def export(self, path=None) -> str:
        """Serialize the ring's spans as JSON-lines; write to ``path`` when
        given.  Every line is one span dict with exactly :data:`SPAN_KEYS`
        keys.  Returns the serialized text either way.
        """
        buf = io.StringIO()
        for s in self.spans():
            buf.write(json.dumps(s.to_dict(), default=str))
            buf.write("\n")
        text = buf.getvalue()
        if path is not None:
            with open(path, "w") as f:
                f.write(text)
        return text


class NullTracer(Tracer):
    """Tracing disabled: same interface, every operation a no-op."""

    def __init__(self):
        super().__init__(capacity=0)

    def span(self, name, **attrs):  # noqa: D102 -- inherited contract
        return contextlib.nullcontext(attrs)

    def record(self, name, t_start, t_end, parent_id=None, **attrs):  # noqa: D102
        return 0

    def add(self, counter, site, value):  # noqa: D102
        pass

    def _append(self, span):
        pass


#: Shared do-nothing tracer -- the default collaborator of instrumented code.
NULL_TRACER = NullTracer()

#: The program's own tracer, always on: the entry points' ``prop.*`` spans
#: and the ``h2d_bytes`` counter (docs/OBSERVABILITY.md has the table).
PROGRAM = Tracer()


def _thread_name() -> str:
    return threading.current_thread().name
