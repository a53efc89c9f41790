"""90th percentile of completion time minus due time. Three samples lie
beyond it in a window of thirty requests, so it stands here, with no bound."""


def read(run):
    return run["end_to_end"].get("request_p90_ms")
