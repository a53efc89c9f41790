"""The traced window split by whether the decode engine had anything to do.

The engine's worker waits for work under ``mx.decode.idle`` spans (``why``:
``empty`` — no request in the engine; ``deferred`` and ``breaker`` are short
yields inside a pass). ``account(run)`` lays the ``empty`` ones against the
traced window (first start to last end of any device operation, as
``trace_reduce.reduce`` has it) and against the lead device's idle intervals:
``window_ns``, ``empty_ns`` (the window under those spans), ``idle_ns`` and
``idle_empty_ns`` (the device's idle time, and its part under them).

``None`` without a trace, and for a program that writes no such span: that it
does is told from an ``mx.decode.idle`` or an ``mx.decode.programs`` span in
the trace (the engine writes the second once every trace), so a window the
engine was never empty in still reads 0 and the parent reads nothing.
"""
from __future__ import annotations

import program_spans

_KEY = "_engine_idle"


def account(run):
    if _KEY not in run:
        run[_KEY] = _account(run)
    return run[_KEY]


def _account(run):
    got = program_spans.load(run)
    if not got or not any(s.name in ("mx.decode.idle", "mx.decode.programs")
                          for s in got["spans"]):
        return None
    devs = run["trace"]["events"]
    lo = min(s for ops in devs.values() for _n, s, _e in ops)
    hi = max(e for ops in devs.values() for _n, _s, e in ops)
    empty = program_spans.nest([
        (s.thread, max(s.start, lo), min(s.end, hi), s.name, s.args)
        for s in got["spans"]
        if s.name == "mx.decode.idle" and s.args.get("why") == "empty"
        and s.end > lo and s.start < hi])
    idle = program_spans.device_idle(run["trace"])
    under = program_spans.idle_by_span(empty, idle)
    return {"window_ns": hi - lo,
            "empty_ns": sum(s.end - s.start for s in empty),
            "idle_ns": sum(e - s for s, e in idle),
            "idle_empty_ns": int(round(
                1e9 * under.get("mx.decode.idle", 0.0)))}
