"""Median time per output token from the engine's own reservoir
(``stats()["tpot_p50_ms"]``, the newest 2048 tokens at the window's end)."""


def read(run):
    return run["counters"].get("tpot_p50_ms")
