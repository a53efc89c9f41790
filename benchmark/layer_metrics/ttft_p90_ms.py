"""90th percentile of first-token time minus due time. Three samples lie
beyond it in a window of thirty requests, so it stands here, with no bound."""


def read(run):
    return run["end_to_end"].get("ttft_p90_ms")
