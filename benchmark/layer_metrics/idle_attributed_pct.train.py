"""Share of the lead device's idle time, in the traced window, that lies
under a child span of ``mx.train.step`` (innermost span wins): how much of
the idle time the program's spans name."""
import program_spans


def read(run):
    return program_spans.idle_share_pct(
        run, lambda name: name.startswith("mx.train.")
        and name != "mx.train.step")
