"""Share of the traced window in which the engine had no request at all: the
window under `mx.decode.idle` spans whose `why` is `empty`."""
import engine_idle


def read(run):
    got = engine_idle.account(run)
    if not got or got["window_ns"] <= 0:
        return None
    return 100.0 * got["empty_ns"] / got["window_ns"]
