"""``DecodeEngine.stats()["slot_occupancy"]`` at the window's end: the mean
share of the engine's slots that held a sequence, per tick."""


def read(run):
    val = run["counters"].get("slot_occupancy")
    return None if val is None else 100.0 * val
