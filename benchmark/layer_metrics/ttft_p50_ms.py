"""Median of first-token time (the ``first_token`` instant of each request's
trace) minus due time. A request waits for the running tick (0-111 ms at the
parent) before its prefill, so over thirty requests this median swings by a
tenth from run to run: it stands here, with no bound."""


def read(run):
    return run["end_to_end"].get("ttft_p50_ms")
