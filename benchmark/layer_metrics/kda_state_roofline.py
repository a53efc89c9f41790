"""Share of its memory roofline that the recurrence of the decode step
reaches. Least time: the state bytes the traced ticks' live slots read and
wrote (``state_bytes_moved`` on ``mx.decode.commit``) over the peak HBM
bandwidth (``flops_ling.state_least_seconds``) — counted from the work,
whatever implements it; device time: what the step's runs spent under
``mx_kda_state``."""
import flops
import flops_ling
import program_parts
import trace_within


def read(run):
    cell = run["cell"]
    ticks = [t for t in trace_within.span_args(run, ("mx.decode.commit",))
             or () if t.get("state_bytes_moved")]
    got = program_parts.load(run) if ticks and cell.peaks else None
    got = got.get(program_parts.STEP) if got else None
    if not got or not got["by_part_ns"].get("mx_kda_state"):
        return None
    least = sum(flops_ling.state_least_seconds(t["state_bytes_moved"],
                                               cell.peaks) for t in ticks)
    return flops.share_of_peak(least, got["by_part_ns"]["mx_kda_state"] / 1e9,
                               "kda_state_roofline")
