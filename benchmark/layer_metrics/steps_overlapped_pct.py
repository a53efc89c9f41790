"""Share of the traced window's decode steps that were dispatched while the
step before them was still un-fetched: `mx.decode.dispatch` spans whose
`overlapped` argument is 1 (`stats()["steps_overlapped"] / ["ticks"]` over
the window)."""
import program_spans


def read(run):
    got = program_spans.load(run)
    if not got:
        return None
    flags = [s.args["overlapped"] for s in got["spans"]
             if s.name == "mx.decode.dispatch" and "overlapped" in s.args]
    if not flags:
        return None
    return 100.0 * sum(flags) / len(flags)
