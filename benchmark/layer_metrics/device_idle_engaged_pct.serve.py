"""The device's idle share while the engine has work: the lead device's idle
time outside the `mx.decode.idle{why=empty}` spans over the traced window
outside them. `device_idle_pct.serve` says whether the window caught an empty
stretch; this says what the engine does with the chip when it has requests."""
import engine_idle


def read(run):
    got = engine_idle.account(run)
    if not got or got["window_ns"] <= got["empty_ns"]:
        return None
    return 100.0 * (got["idle_ns"] - got["idle_empty_ns"]) \
        / (got["window_ns"] - got["empty_ns"])
