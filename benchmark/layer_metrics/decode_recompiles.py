"""``DecodeEngine.stats()["steady_state_recompiles"]`` after the run."""


def read(run):
    return run["counters"].get("decode_recompiles")
