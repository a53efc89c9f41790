"""Share of the lead device's idle time, in the traced window, that lies
under an ``mx.decode.*`` span of the engine's worker."""
import program_spans


def read(run):
    return program_spans.idle_share_pct(
        run, lambda name: name.startswith("mx.decode."))
