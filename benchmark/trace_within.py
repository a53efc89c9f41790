"""Device time of named operations INSIDE the runs of one program: a kernel
that several programs call (the grouped product runs in the decode step and
in every prefill rung) is split by the program run that encloses it."""
from __future__ import annotations

import bisect
import re


def time_within(reduced, pattern, module):
    """``(seconds, events, runs)``: summed device time and count of the lead
    device's operations whose full name matches ``pattern`` and that start
    inside a run of a program whose name holds ``module``; and how many such
    runs the trace holds."""
    rx = re.compile(pattern)
    runs = sorted((s, e) for name, s, e in reduced["modules"]
                  if module in name)
    starts = [s for s, _e in runs]
    total, count = 0, 0
    for name, s, e in reduced["events"][reduced["lead_device"]]:
        if not rx.search(name):
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < runs[i][1]:
            total += e - s
            count += 1
    return total / 1e9, count, len(runs)


def span_args(run, names):
    """The ``args`` of the traced spans called one of ``names`` (by start);
    ``None`` without a trace or without such spans."""
    import program_spans

    got = program_spans.load(run)
    if not got:
        return None
    rows = [s.args for s in sorted(got["spans"], key=lambda s: s.start)
            if s.name in names]
    return rows or None
