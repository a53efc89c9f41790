"""Median host time of ``nd.array(batch, ctx=...)`` for data and label (the
harness's ``bench.feed`` span): the per-step host-to-device placement."""


def read(run):
    return run.get("span_medians_ms", {}).get("bench.feed")
