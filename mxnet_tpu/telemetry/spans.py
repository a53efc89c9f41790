"""Span tracing: one instrumentation point, three sinks.

``telemetry.span("name")`` times a region and publishes it to

* the metrics registry — ``mxnet_span_duration_ms{category=,span=}``
  summary series (p50/p90/p99 over the recent window),
* the profiler event buffer — a chrome://tracing complete event in the
  same ``category`` lane as the rest of the framework's events, and
* the ``jax.profiler`` trace — a ``jax.profiler.TraceAnnotation`` named
  ``"mx." + name`` on the calling thread's line of the ``/host:`` plane,
  with the span's keyword arguments as its stats: the same file and the
  same clock as the device's ``XLA Ops``, so an idle gap of the device
  can be laid against the program span that covered it,

so a region instrumented once shows up on a Prometheus scrape, in the
chrome trace of a profiling session and in XProf. Each sink keeps its own
switch: the registry records iff ``MXNET_TELEMETRY`` is on, the event
buffer iff a ``profiler.set_state('run')`` session is live, the
annotation iff a ``jax.profiler`` trace is being taken (the profiler's own
check: ``profiler.set_state('run')`` starts one when ``profile_all`` or
``profile_symbolic`` is configured, and so does anyone who calls
``jax.profiler.start_trace``); with all off the span costs two
module-global reads and that check, and no clock call.

Use as a context manager, a decorator, or both::

    with telemetry.span("load_checkpoint"):
        ...

    @telemetry.span("kvstore.push", category="kvstore")
    def push(...): ...

:func:`traced` is the dynamic-label variant for call sites whose span name
depends on the arguments (the executor's ``forward(<symbol>)``).
"""
from __future__ import annotations

import functools
import time

from jax.profiler import TraceAnnotation as _Annotation

from .. import profiler as _profiler
from . import registry as _registry

__all__ = ["span", "traced", "trace_live", "SPAN_MS", "TRACE_PREFIX"]

#: What a span's annotation in the ``jax.profiler`` trace is named with:
#: every host event of this framework starts with it.
TRACE_PREFIX = "mx."

#: whether a ``jax.profiler`` trace is being taken (one atomic read)
trace_live = _trace_live = _Annotation.is_enabled

#: Every span's duration lands here; ``category`` groups related spans
#: (executor/kvstore/serving/…), ``span`` is the specific region.
SPAN_MS = _registry.histogram(
    "mxnet_span_duration_ms",
    "duration of telemetry.span regions in milliseconds",
    labels=("category", "span"))


class span:
    """Timed region feeding the registry, the profiler event buffer and
    the ``jax.profiler`` trace. ``args`` go to the trace alone (the
    registry's labels stay ``category`` and ``span``)."""

    __slots__ = ("name", "category", "args", "_t0", "_ann")

    def __init__(self, name: str, category: str = "span", **args):
        self.name = name
        self.category = category
        self.args = args
        self._t0 = None
        self._ann = None

    def __enter__(self):
        if _trace_live():
            self._ann = _Annotation(TRACE_PREFIX + self.name, **self.args)
            self._ann.__enter__()
        if _registry.ENABLED or _profiler.ENABLED:
            self._t0 = time.perf_counter()
        return self

    def set_args(self, **args):
        """Arguments known only inside the region (what an admission pass
        found, say): added to the open annotation, dropped without one."""
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def __exit__(self, *exc):
        ann = self._ann
        if ann is not None:
            self._ann = None
            ann.__exit__(None, None, None)
        t0 = self._t0
        if t0 is None:
            return False
        self._t0 = None
        dur_s = time.perf_counter() - t0
        if _registry.ENABLED:
            SPAN_MS.observe(dur_s * 1e3, category=self.category,
                            span=self.name)
        # record_event re-checks profiler.ENABLED itself (it may have been
        # paused while the span was open)
        _profiler.record_event(self.name, self.category, t0 * 1e6,
                               dur_s * 1e6)
        return False

    def __call__(self, fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if not (_registry.ENABLED or _profiler.ENABLED
                    or _trace_live()):
                return fn(*args, **kwargs)
            with span(self.name, self.category, **self.args):
                return fn(*args, **kwargs)

        return wrapper


def traced(category: str, label):
    """Decorator variant of :class:`span` for dynamic names: ``label`` is a
    string or a callable over the wrapped function's arguments. Supersedes
    ``profiler.profiled`` at framework call sites — same event-buffer
    output, plus the registry histogram and the trace annotation."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if not (_registry.ENABLED or _profiler.ENABLED
                    or _trace_live()):
                return fn(*args, **kwargs)
            lbl = label(*args, **kwargs) if callable(label) else label
            with span(lbl, category):
                return fn(*args, **kwargs)

        return wrapper

    return deco
