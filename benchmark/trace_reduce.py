"""From a profiler trace (``*.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` and nothing else. A device plane is
``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per executed HLO
operation (start and duration in ns), which is what "an operation ran on the
device" means here. Host spans are the ``bench.*`` events the harness writes
with ``jax.profiler.TraceAnnotation``; they sit on the host plane's thread
lines, on the same clock.
"""
from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_SPAN_PREFIX = "bench."


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError("no *.xplane.pb under %s" % trace_dir)
    return files[-1]


def short_name(event_name):
    """``%fusion.16 = (...) fusion(...)`` -> ``%fusion.16``."""
    return event_name.split(" = ", 1)[0].strip()[:96]


def _union_ns(intervals):
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _gaps(intervals, lo, hi):
    """Idle intervals inside [lo, hi] between the merged busy intervals."""
    out, cursor = [], lo
    for s, e in sorted(intervals):
        if s > cursor:
            out.append((cursor, min(s, hi)))
        cursor = max(cursor, e)
        if cursor >= hi:
            break
    if cursor < hi:
        out.append((cursor, hi))
    return [(s, e) for s, e in out if e > s]


def load(path):
    """``{"devices": {ordinal: [(name, start_ns, end_ns), ...]}, "modules":
    the same for whole executed programs, "host_spans": [(name, start_ns,
    end_ns), ...]}`` of one trace file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, modules, host = {}, {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                           for ev in line.events]
                    (devices if line.name == OPS_LINE
                     else modules)[int(m.group(1))] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    return {"devices": devices, "modules": modules, "host_spans": host}


def reduce(path, top=10):
    """The reduction the harness reports. Window = first start to last end
    of any device operation in the trace (the traced steady span). Busy is
    the union of the operation intervals, per device, averaged over the
    devices that ran anything. ``ops`` sums durations by short name on the
    busiest device; ``idle_gaps`` are that device's longest gaps, each with
    the host span that covered most of it. A trace in which no operation
    ran on any device gives ``busy_s`` 0 and no events."""
    raw = load(path)
    devs = {d: ops for d, ops in raw["devices"].items() if ops}
    if not devs:
        # an empty traced span is a reading, not a crash: busy 0, no events;
        # run.py ends the run with its own "no operation ran" message
        return {"window_s": 0.0, "busy_s": 0.0, "devices": [],
                "lead_device": None, "ops_s": {}, "device_ops": [],
                "idle_gaps": [], "events": {}, "modules": [],
                "host_spans": raw["host_spans"]}
    lo = min(s for ops in devs.values() for _n, s, _e in ops)
    hi = max(e for ops in devs.values() for _n, _s, e in ops)
    window_ns = hi - lo
    busy = {d: _union_ns([(s, e) for _n, s, e in ops])
            for d, ops in devs.items()}
    busy_ns = sum(busy.values()) / len(busy)
    lead = max(busy, key=busy.get)
    by_name = {}
    for name, s, e in devs[lead]:
        key = short_name(name)
        by_name[key] = by_name.get(key, 0) + (e - s)
    gaps = sorted(_gaps([(s, e) for _n, s, e in devs[lead]], lo, hi),
                  key=lambda g: g[0] - g[1])[:top]
    named_gaps = []
    for s, e in gaps:
        best, best_ov = "(no bench span)", 0
        for name, hs, he in raw["host_spans"]:
            ov = min(e, he) - max(s, hs)
            if ov > best_ov:
                best, best_ov = name, ov
        named_gaps.append([best, (e - s) / 1e9])
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / 1e9,
        "devices": sorted(devs),
        "lead_device": lead,
        "ops_s": {k: v / 1e9 for k, v in by_name.items()},
        "device_ops": [[k, v / 1e9] for k, v in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": named_gaps,
        "events": devs,
        "modules": raw["modules"].get(lead, []),
        "host_spans": raw["host_spans"],
    }


def time_matching(reduced, pattern, device=None):
    """Summed device seconds, and event count, of the operations on one
    device (default: the lead) whose FULL event name matches ``pattern``."""
    rx = re.compile(pattern)
    dev = reduced["lead_device"] if device is None else device
    hits = [(e - s) for name, s, e in reduced["events"][dev]
            if rx.search(name)]
    return sum(hits) / 1e9, len(hits)


def dominant_module(reduced):
    """``(name, runs, seconds)`` of the program that took most device time
    on the lead device: a train step, a decode tick."""
    by_name = {}
    for name, s, e in reduced["modules"]:
        key = name.split("(", 1)[0]
        runs, secs = by_name.get(key, (0, 0))
        by_name[key] = (runs + 1, secs + (e - s))
    if not by_name:
        return None
    key = max(by_name, key=lambda k: by_name[k][1])
    return key, by_name[key][0], by_name[key][1] / 1e9
