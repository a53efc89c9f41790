"""``telemetry.RECOMPILES{site=trainplane.step}`` inside the window."""


def read(run):
    return run["counters"].get("train_recompiles")
