"""How late the load generator ran: submit time minus due time, 90th
percentile over the window's requests. A starved generator would otherwise
read as a fast server."""


def read(run):
    return run["counters"].get("gen_late_p90_ms")
