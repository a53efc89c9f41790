"""The program's own spans, read from the profiler's trace.

``mxnet_tpu.telemetry.span`` writes every region it times into a live
``jax.profiler`` trace as a ``TraceAnnotation`` named ``mx.<name>``, on the
calling thread's line of a ``/host:`` plane, with the span's keyword arguments
as the event's stats. That is the file and the clock of the device's ``XLA
Ops``, so a span can be laid against the device's idle intervals.

Contract with the per-layer readers (``benchmark/layer_metrics/*.py``):

* ``load(run)`` gives ``None`` when ``run["trace"]`` is ``None`` (a
  rehearsal) or the traced run left no ``*.xplane.pb`` under
  ``run["cell"].trace_dir`` (``run.py`` removes that directory only after
  the readers ran). Otherwise it reads the file once a run, keeps the
  result on ``run`` and prints one line ``{"phase": "program_spans", ...}``.
* A program that writes no ``mx.*`` event (the parent of the PR that brought
  the spans) gives empty tables: ``span_stat`` and the others then return
  ``None``, the reader returns ``None`` and the metric is left out.

What ``load`` returns:

``spans``
    ``[Span]``: name, start and end (ns, the trace's clock), ``args``,
    ``thread`` (the line's index), ``parent`` (index of the innermost span of
    the same thread that encloses it, or ``None``) and ``self_ns`` (its
    duration minus that of its direct children).
``by_name``
    per span name ``count``, ``median_ms``, ``total_ms``, ``longest_ms`` and,
    for a name that has children somewhere, ``self_median_ms`` and
    ``self_total_ms``.
``idle_s``
    the lead device's idle seconds inside the traced window (first start to
    last end of any device operation, as ``trace_reduce.reduce`` has it).
``idle_by_span_s``
    those idle seconds by the INNERMOST ``mx.*`` span that covered them;
    what no span covered is under ``"(no mx span)"``. Where spans of two
    threads cover one instant, the stretch that started first keeps it, so
    the shares add up to ``idle_s``.

The idle intervals (``_gaps``) and the look for the file are ``trace_reduce``'s.
"""
from __future__ import annotations

import collections
import json
import statistics

import trace_reduce

PREFIX = "mx."
UNCOVERED = "(no mx span)"
_KEY = "_program_spans"

Span = collections.namedtuple(
    "Span", "name start end args thread parent self_ns")


def read_spans(path):
    """The ``mx.*`` events of the ``/host:`` planes of one trace file as
    ``[Span]``, nested per thread."""
    from jax.profiler import ProfileData

    raw, thread = [], 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            thread += 1
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    start = int(ev.start_ns)
                    raw.append((thread, start, start + int(ev.duration_ns),
                                ev.name, dict(ev.stats)))
    return nest(raw)


def nest(raw):
    """``[(thread, start, end, name, args)]`` -> ``[Span]`` with parents and
    self times. A span's parent is the innermost earlier span of its thread
    that has not ended when it starts."""
    raw = sorted(raw, key=lambda r: (r[0], r[1], -r[2]))
    parents, children_ns, stack = [], [0] * len(raw), []
    for i, (thread, start, end, _name, _args) in enumerate(raw):
        while stack and (raw[stack[-1]][0] != thread
                         or raw[stack[-1]][2] <= start):
            stack.pop()
        parents.append(stack[-1] if stack else None)
        if stack:
            children_ns[stack[-1]] += end - start
        stack.append(i)
    return [Span(name, start, end, args, thread, parents[i],
                 (end - start) - children_ns[i])
            for i, (thread, start, end, name, args) in enumerate(raw)]


def table(spans):
    """``{name: {count, median_ms, total_ms, longest_ms[, self_median_ms,
    self_total_ms]}}``."""
    has_children = {spans[s.parent].name for s in spans
                    if s.parent is not None}
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    out = {}
    for name, rows in sorted(by_name.items()):
        durs = [(s.end - s.start) / 1e6 for s in rows]
        out[name] = {"count": len(rows), "median_ms": statistics.median(durs),
                     "total_ms": sum(durs), "longest_ms": max(durs)}
        if name in has_children:
            selfs = [s.self_ns / 1e6 for s in rows]
            out[name]["self_median_ms"] = statistics.median(selfs)
            out[name]["self_total_ms"] = sum(selfs)
    return out


def innermost_segments(spans):
    """Non-overlapping ``[(start, end, name)]``, sorted: every instant some
    span covers, under the innermost span of the thread that covers it
    (where threads overlap, the stretch that started first keeps it)."""
    children = {}
    for i, s in enumerate(spans):
        children.setdefault(s.parent, []).append(i)
    segs = []
    for i, s in enumerate(spans):
        cursor = s.start
        for c in children.get(i, ()):
            if spans[c].start > cursor:
                segs.append((cursor, spans[c].start, s.name))
            cursor = max(cursor, spans[c].end)
        if s.end > cursor:
            segs.append((cursor, s.end, s.name))
    out, cursor = [], None
    for start, end, name in sorted(segs):
        if cursor is not None and start < cursor:
            start = cursor
        if end > start:
            out.append((start, end, name))
            cursor = end
    return out


def idle_by_span(spans, idle):
    """Seconds of the ``idle`` intervals (``[(start_ns, end_ns)]``) by the
    innermost span that covered them; the rest under ``UNCOVERED``."""
    segs = innermost_segments(spans)
    out, j = {}, 0
    for lo, hi in sorted(idle):
        covered = 0
        while j < len(segs) and segs[j][1] <= lo:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < hi:
            ov = min(hi, segs[k][1]) - max(lo, segs[k][0])
            if ov > 0:
                out[segs[k][2]] = out.get(segs[k][2], 0) + ov
                covered += ov
            k += 1
        if hi - lo > covered:
            out[UNCOVERED] = out.get(UNCOVERED, 0) + (hi - lo) - covered
    return {name: ns / 1e9 for name, ns in out.items()}


def device_idle(reduced):
    """The lead device's idle intervals inside the traced window."""
    devs = reduced["events"]
    lo = min(s for ops in devs.values() for _n, s, _e in ops)
    hi = max(e for ops in devs.values() for _n, _s, e in ops)
    return trace_reduce._gaps(
        [(s, e) for _n, s, e in devs[reduced["lead_device"]]], lo, hi)


def load(run):
    """See the module's docstring. ``None`` without a trace."""
    if run.get("trace") is None:
        return None
    if _KEY not in run:
        try:
            path = trace_reduce.find_xplane(run["cell"].trace_dir)
        except FileNotFoundError:
            run[_KEY] = None
            return None
        spans = read_spans(path)
        idle = device_idle(run["trace"])
        by_span = idle_by_span(spans, idle)
        run[_KEY] = {
            "spans": spans, "by_name": table(spans),
            "idle_s": sum(e - s for s, e in idle) / 1e9,
            "idle_by_span_s": by_span}
        print(json.dumps({
            "phase": "program_spans", "spans": run[_KEY]["by_name"],
            "idle_s": run[_KEY]["idle_s"], "idle_by_span_s": by_span},
            sort_keys=True), flush=True)
    return run[_KEY]


def span_stat(run, name, key="median_ms"):
    """One number of ``by_name[name]`` (the median by default), or ``None``
    where the run has no trace or no such span."""
    got = load(run)
    row = got["by_name"].get(name) if got else None
    return None if row is None else row.get(key)


def idle_share_pct(run, covered):
    """Share (%) of the lead device's idle time under the innermost spans
    whose name ``covered(name)`` accepts; ``None`` without spans."""
    got = load(run)
    if not got or not got["spans"] or got["idle_s"] <= 0:
        return None
    under = sum(s for name, s in got["idle_by_span_s"].items()
                if covered(name))
    return 100.0 * under / got["idle_s"]


def children_of(got, parent_name):
    """``[(parent Span, [child Span])]`` for every span named
    ``parent_name``, children being its direct ones."""
    spans = got["spans"]
    kids = {}
    for s in spans:
        if s.parent is not None:
            kids.setdefault(s.parent, []).append(s)
    return [(s, kids.get(i, [])) for i, s in enumerate(spans)
            if s.name == parent_name]
