"""Nestable span timers for the serving stack's host-side phases.

A :class:`Tracer` times named spans -- admission and its parts, group-step
dispatch and wait, AOT compiles, decodes, the driver's inbox and hand-back
-- and feeds each duration into a per-span-path histogram of a
:class:`~repro.obs.metrics.MetricsRegistry`. Spans nest (``admit`` >
``form`` > ``prior``); the tracer keeps a thread-local stack so the
recorded name is the dotted path of its ancestry (``admit.form.prior``),
which is what ``docs/observability.md`` documents as the span hierarchy.

Two hard rules, both about the jitted hot path:

* spans time HOST-side work only. A span around an executor call measures
  dispatch (and whatever the caller chooses to block on), never forces a
  device sync itself -- there is no ``block_until_ready`` anywhere in this
  module.
* with ``annotate=True`` each span also enters a
  ``jax.profiler.TraceAnnotation`` named by its dotted path, carrying the
  span's keyword arguments as event stats, so the same names show up on the
  device trace's clock in XLA/perfetto profiles. The annotation is a no-op
  unless a profiler trace is being collected; it adds no sync either.
  Without ``annotate`` the keyword arguments are dropped unread.

``NULL_TRACER`` is the disabled instance: its ``span()`` is a reusable
no-op context manager, so instrumented code never branches on "is tracing
on" -- it just always runs ``with tracer.span(...):``.
"""
from __future__ import annotations

import threading
import time
from typing import Optional

from jax.profiler import TraceAnnotation

from .metrics import DEFAULT_TIME_EDGES, Histogram, MetricsRegistry


class _NullSpan:
    """Reusable no-op context manager (one shared instance, zero alloc)."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """One timed span: perf_counter on enter/exit, duration observed into
    the tracer's histogram for the span's dotted path. The parent path is
    carried explicitly (not recomputed from the dotted string) so span
    NAMES may themselves contain dots."""
    __slots__ = ("_tracer", "_path", "_parent", "_args", "_t0", "_ann")

    def __init__(self, tracer: "Tracer", path: str, parent: str,
                 args: Optional[dict]):
        self._tracer = tracer
        self._path = path
        self._parent = parent
        self._args = args
        self._ann = None

    def __enter__(self):
        tr = self._tracer
        tr._stack.path = self._path
        if self._args is not None:
            self._ann = TraceAnnotation(self._path, **self._args)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        tr = self._tracer
        if self._ann is not None:
            self._ann.__exit__(*exc)
        tr._observe(self._path, dt)
        tr._stack.path = self._parent
        return False


class Tracer:
    """Span-timer bound to a metrics registry.

    ``tracer.span("form")`` inside ``tracer.span("admit")`` records into the
    histogram ``<prefix>admit.form_seconds`` -- one histogram per distinct
    path, registered on its first use and kept by the tracer, so a span's
    exit never takes the registry's lock. The nesting stack is
    thread-local, so transport threads and the scheduler thread can trace
    concurrently without mixing ancestries.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None, *,
                 prefix: str = "trace_", annotate: bool = False,
                 edges=DEFAULT_TIME_EDGES):
        self.registry = registry or MetricsRegistry()
        self.prefix = prefix
        self.annotate = annotate
        self._edges = edges
        self._hists: dict[str, Histogram] = {}
        self._stack = threading.local()
        self._stack.path = ""

    # thread-local access: a thread that never opened a span has no .path
    def _current(self) -> str:
        return getattr(self._stack, "path", "")

    def span(self, name: str, **args) -> _Span:
        """A span named ``name`` under the thread's open span. ``args``
        (numbers or strings) ride on the profiler annotation when
        ``annotate`` is on, and are dropped otherwise."""
        parent = self._current()
        return _Span(self, f"{parent}.{name}" if parent else name, parent,
                     args if self.annotate else None)

    def _observe(self, path: str, dt: float) -> None:
        h = self._hists.get(path)
        if h is None:
            h = self._hists[path] = self.registry.histogram(
                f"{self.prefix}{path}_seconds",
                help=f"span duration: {path}", edges=self._edges)
        h.observe(dt)

    def span_names(self) -> list[str]:
        """Dotted span paths recorded so far (for tests/docs)."""
        pre, suf = self.prefix, "_seconds"
        return sorted(m.name[len(pre):-len(suf)] for m in self.registry
                      if m.name.startswith(pre) and m.name.endswith(suf))


class _NullTracer(Tracer):
    """Disabled tracer: ``span()`` returns a shared no-op context manager."""

    def __init__(self):
        super().__init__(MetricsRegistry())

    def span(self, name: str, **args):  # type: ignore[override]
        return _NULL_SPAN


NULL_TRACER = _NullTracer()
