"""Context-manager span tracing with Chrome-trace export.

A span is a named, attributed interval on the monotonic clock
(``time.monotonic_ns`` — wall-clock jumps can never produce negative
durations).  Nesting follows ``with`` structure within a thread: each
thread keeps its own open stack, a span entered while another span of the
same thread is open becomes its child, and the roots form the trace.  So
a client thread's span never nests under another thread's.  The
taxonomy the repo emits is documented in DESIGN.md §12.4.

Profiler clock: while it is open, an enabled span also holds a
``jax.profiler.TraceAnnotation`` of its name with its attributes as
stats, so a ``jax.profiler`` trace shows it on the host plane beside the
device's ops, on the same clock.  Sequence attributes (``jobs``) go to the
profiler as one space-separated string.  Attributes set inside the span
(``set_attr``) reach the in-memory tree only.  JAX is imported on the
first enabled span, not at import time.

Disabled-path contract: ``Tracer.span()`` returns a shared no-op context
manager when the switch is off — no span object is allocated, entering
and exiting it does nothing.  Attributes are therefore passed as an
optional dict argument (``span("engine.step", {"k": 8})``), not as
``**kwargs``, so a disabled call site does not even build a dict.

Export is Chrome-trace JSON (``chrome://tracing`` / Perfetto "trace event
format", complete events): timestamps and durations in microseconds,
attributes in ``args``.  Nesting round-trips through the flat event list
by interval containment — tests/test_obs.py reconstructs the tree from a
dumped trace and checks it against the structured ``as_dict`` export.

The tracer bounds memory: past ``max_spans`` recorded spans, new spans
are counted in ``dropped`` instead of stored (a serving process must not
grow a trace forever).
"""
from __future__ import annotations

import json
import threading
import time
from typing import Dict, List, Optional

from repro.obs.runtime import SWITCH


class Span:
    """One timed interval; a context manager bound to its tracer."""

    __slots__ = ("name", "attrs", "start_ns", "end_ns", "children", "tid",
                 "_tracer", "_annotation")

    def __init__(self, tracer: "Tracer", name: str,
                 attrs: Optional[Dict[str, object]] = None):
        self.name = name
        self.attrs: Dict[str, object] = {} if attrs is None else dict(attrs)
        self.start_ns = 0
        self.end_ns = 0
        self.children: List[Span] = []
        self.tid = 0
        self._tracer = tracer
        self._annotation = None

    def set_attr(self, key: str, value: object) -> None:
        """Attach a result computed inside the span (e.g. a chosen format)."""
        self.attrs[key] = value

    @property
    def duration_s(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    def __enter__(self) -> "Span":
        from jax.profiler import TraceAnnotation
        self.tid = threading.get_ident()
        self._tracer._push(self)
        self._annotation = TraceAnnotation(
            self.name, **{k: _stat(v) for k, v in self.attrs.items()})
        self._annotation.__enter__()
        self.start_ns = time.monotonic_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = time.monotonic_ns()
        self._annotation.__exit__(*exc)
        self._annotation = None
        self._tracer._pop(self)

    def as_dict(self) -> dict:
        return dict(name=self.name, attrs=dict(self.attrs),
                    start_us=self.start_ns / 1e3,
                    dur_us=(self.end_ns - self.start_ns) / 1e3,
                    children=[c.as_dict() for c in self.children])


def _stat(value: object) -> object:
    """A span attribute as a profiler stat: sequences joined by spaces
    (the profiler's own encoding separates attributes by commas)."""
    if isinstance(value, (list, tuple)):
        return " ".join(str(v) for v in value)
    return value


class _NoopSpan:
    """Shared disabled-path span: allocation-free enter/exit/set_attr."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None

    def set_attr(self, key, value):
        return None


_NOOP_SPAN = _NoopSpan()


class Tracer:
    """Span factory + one open-span stack per thread + the finished-span
    forest."""

    def __init__(self, max_spans: int = 100_000):
        self.roots: List[Span] = []
        self.dropped = 0
        self.max_spans = max_spans
        self._local = threading.local()
        self._lock = threading.Lock()
        self._recorded = 0

    def span(self, name: str,
             attrs: Optional[Dict[str, object]] = None):
        """Open a span: ``with tracer.span("engine.step", {"k": 8}):``.

        Returns the shared no-op context manager when observability is
        disabled."""
        if not SWITCH.on:
            return _NOOP_SPAN
        return Span(self, name, attrs)

    # -- stack maintenance (called by Span.__enter__/__exit__) -------------
    def _stack(self) -> List[Span]:
        """The calling thread's open spans, outermost first."""
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _push(self, span: Span) -> None:
        self._stack().append(span)

    def _pop(self, span: Span) -> None:
        stack = self._stack()
        # tolerate interleaved exits (generators, exceptions): unwind to
        # the span being closed rather than assuming strict LIFO
        while stack:
            top = stack.pop()
            if top is span:
                break
        with self._lock:
            if self._recorded >= self.max_spans:
                self.dropped += 1
                return
            if stack:
                stack[-1].children.append(span)
            else:
                self.roots.append(span)
            self._recorded += 1

    # -- export ------------------------------------------------------------
    def export(self) -> List[dict]:
        """Structured (nested) dump of every finished root span."""
        return [s.as_dict() for s in self.roots]

    def export_chrome(self) -> List[dict]:
        """Flat Chrome-trace complete events (``ph: "X"``, microseconds)."""
        events: List[dict] = []

        def walk(span: Span) -> None:
            events.append(dict(
                name=span.name, ph="X", pid=0, tid=span.tid,
                ts=span.start_ns / 1e3,
                dur=(span.end_ns - span.start_ns) / 1e3,
                args=dict(span.attrs)))
            for c in span.children:
                walk(c)

        for root in self.roots:
            walk(root)
        return events

    def to_chrome_json(self) -> str:
        return json.dumps({"traceEvents": self.export_chrome()})

    def reset(self) -> None:
        self.roots.clear()
        self._local = threading.local()       # every thread's open stack
        self.dropped = 0
        self._recorded = 0
