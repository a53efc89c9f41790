"""Summed device time of the all-reduce operations on one chip per traced
step (steps = runs of the dominant program in the traced window)."""
import trace_reduce


def read(run):
    if run["trace"] is None:
        return None
    seconds, count = trace_reduce.time_matching(run["trace"], r"all-reduce")
    mod = trace_reduce.dominant_module(run["trace"])
    if not count or mod is None:
        return None
    return 1e3 * seconds / mod[1]
