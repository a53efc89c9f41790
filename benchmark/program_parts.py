"""Device time of the decode engine's programs by the named part of the
program each operation belongs to.

The engine writes, once a ``jax.profiler`` trace, one zero-length span
``mx.decode.programs`` a compiled program (the decode step, each prefill
and chunk rung): ``program`` (what an ``XLA Modules`` event of that program starts
with), ``rung`` (a prefill's), ``parts`` (one JSON string ``{part:
[instruction names]}``, from ``mxnet_tpu.telemetry.program_parts`` over the
compiled text), ``mixed``, ``unnamed``, ``map_us`` (what reading the map cost the engine)
and, on the step, ``step_temp_bytes``. An ``XLA Ops`` event is
named by its instruction (``trace_reduce.short_name``), so the join is a
lookup: this module lays the lead device's operations that start inside a run
of each program against that program's map.

Contract with the readers (``benchmark/layer_metrics/*.py``): ``load(run)``
gives ``None`` without a trace, without such spans (the parent of the PR that
brought them) or where no map names anything (an executable read back from a
compile cache that a program without the scopes filled keeps that program's
``op_name``s); otherwise it joins once a run, keeps the result on ``run`` and
prints one line ``{"phase": "program_parts", ...}``: per program its runs,
device ms a run, ms a run by part, unnamed ms a run and the five largest
unnamed instructions.

What ``load`` returns, per program name: ``runs``, ``run_ns`` (each run's
device duration), ``ops_ns`` (summed SELF time of the operations inside the
runs: an operation that holds others, a ``while`` around its body, counts
what its children do not), ``by_part_ns``, ``unnamed_ns`` (the two add up to
``ops_ns``: a partition), ``top_unnamed`` and the map's own ``mixed`` /
``map_unnamed`` / ``map_us``. Runs of ``jit_mx_prefill`` take the map of the
rung whose ``mx.decode.prefill`` span covers their start, else all rungs'
maps laid over each other.
"""
from __future__ import annotations

import bisect
import json
import statistics

import program_spans
import trace_reduce

SPAN = "mx.decode.programs"
STEP = "jit_mx_decode_step"
PREFILL = "jit_mx_prefill"
_KEY = "_program_parts"


def maps_of(spans):
    """``{program: {rung or None: {"parts": {instruction: part}, ...the
    span's other arguments}}}`` of the ``mx.decode.programs`` spans."""
    out = {}
    for s in spans:
        if s.name != SPAN:
            continue
        row = dict(s.args)
        row["parts"] = {inst: part
                        for part, insts in json.loads(row["parts"]).items()
                        for inst in insts}
        out.setdefault(row["program"], {})[row.get("rung")] = row
    return out


def self_times(events):
    """``[(name, start_ns, self_ns)]`` by start: an event's duration minus
    that of the events of the same line that lie directly inside it
    (``program_spans.nest``'s arithmetic, the line taken for one thread)."""
    return [(s.name, s.start, max(0, s.self_ns)) for s in program_spans.nest(
        [(0, start, end, name, None) for name, start, end in events])]


def join(reduced, spans):
    """See the module's docstring; ``{}`` where no program has a map that
    names anything."""
    rungs = sorted((s.start, s.end, s.args["rung"]) for s in spans
                   if s.name == "mx.decode.prefill" and "rung" in s.args)
    rung_starts = [r[0] for r in rungs]
    events = self_times(reduced["events"][reduced["lead_device"]])
    starts = [ev[1] for ev in events]
    out = {}
    for program, by_rung in maps_of(spans).items():
        if not any(row["parts"] for row in by_rung.values()):
            continue
        merged = {}
        for row in by_rung.values():
            for inst, part in row["parts"].items():
                merged.setdefault(inst, part)
        got = {"runs": 0, "run_ns": [], "ops_ns": 0, "by_part_ns": {},
               "unnamed_ns": 0,
               "mixed": sum(r["mixed"] for r in by_rung.values()),
               "map_unnamed": sum(r["unnamed"] for r in by_rung.values()),
               "map_us": sum(r["map_us"] for r in by_rung.values())}
        unnamed = {}
        for name, lo, hi in sorted(reduced["modules"], key=lambda m: m[1]):
            if name.split("(", 1)[0] != program:
                continue
            got["runs"] += 1
            got["run_ns"].append(hi - lo)
            parts = merged
            k = bisect.bisect_right(rung_starts, lo) - 1
            if None not in by_rung and k >= 0 and lo < rungs[k][1] \
                    and rungs[k][2] in by_rung:
                parts = by_rung[rungs[k][2]]["parts"]
            i = bisect.bisect_left(starts, lo)
            while i < len(events) and starts[i] < hi:
                inst = trace_reduce.short_name(events[i][0]).lstrip("%")
                ns = events[i][2]
                got["ops_ns"] += ns
                part = parts.get(inst)
                if part is None:
                    got["unnamed_ns"] += ns
                    unnamed[inst] = unnamed.get(inst, 0) + ns
                else:
                    got["by_part_ns"][part] = \
                        got["by_part_ns"].get(part, 0) + ns
                i += 1
        got["top_unnamed"] = sorted(unnamed.items(),
                                    key=lambda kv: -kv[1])[:5]
        out[program] = got
    return out


def load(run):
    """See the module's docstring. ``None`` with nothing to read."""
    if _KEY not in run:
        spans = program_spans.load(run)
        run[_KEY] = join(run["trace"], spans["spans"]) or None \
            if spans else None
        if run[_KEY]:
            print(json.dumps({"phase": "program_parts", "programs": {
                program: {
                    "runs": got["runs"],
                    "device_ms_a_run": _per(sum(got["run_ns"]), got),
                    "ops_ms_a_run": _per(got["ops_ns"], got),
                    "ms_a_run_by_part": {
                        part: _per(ns, got)
                        for part, ns in sorted(got["by_part_ns"].items())},
                    "unnamed_ms_a_run": _per(got["unnamed_ns"], got),
                    "top_unnamed_ms": [[inst, ns / 1e6]
                                       for inst, ns in got["top_unnamed"]],
                    "mixed_fusions": got["mixed"],
                    "map_unnamed": got["map_unnamed"],
                    "map_s": got["map_us"] / 1e6}
                for program, got in run[_KEY].items()}}, sort_keys=True),
                flush=True)
    return run[_KEY]


def _per(ns, got):
    return ns / 1e6 / got["runs"] if got["runs"] else None


def _program(run, program):
    got = load(run)
    got = got.get(program) if got else None
    return got if got and got["runs"] and got["ops_ns"] else None


def part_ms_a_run(run, program, parts):
    """Device ms a run of ``program`` under the ``parts`` (a tuple of names,
    or a prefix string); ``None`` with nothing to read."""
    got = _program(run, program)
    return None if got is None else _per(_under(got, parts), got)


def part_share_pct(run, program, parts):
    """The same as a share (%) of the operations' time in those runs."""
    got = _program(run, program)
    return None if got is None else 100.0 * _under(got, parts) / got["ops_ns"]


def unnamed_pct(run, program):
    """Share (%) of the operations' time in the runs of ``program`` that the
    map puts under no part."""
    got = _program(run, program)
    return None if got is None else 100.0 * got["unnamed_ns"] / got["ops_ns"]


def run_ms_p50(run, program):
    """Median device duration (ms) of the runs of ``program``."""
    got = _program(run, program)
    return None if got is None else statistics.median(got["run_ns"]) / 1e6


def _under(got, parts):
    if isinstance(parts, str):
        return sum(ns for part, ns in got["by_part_ns"].items()
                   if part.startswith(parts))
    return sum(got["by_part_ns"].get(part, 0) for part in parts)
