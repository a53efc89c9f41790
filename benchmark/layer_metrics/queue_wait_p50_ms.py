"""Median ``queue_wait_ms`` of the ``admit`` event of the request traces
minted after the set-up: submit to the admission pass that seated the
request (pages, slots and the running tick are what it waits for)."""
import stats as stats_mod


def read(run):
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import tracing

    if run["trace"] is None:
        return None
    t_setup = run["cell"].t_setup_done
    waits = []
    for tid in tracing.trace_ids():
        events = (telemetry.get_trace(tid) or {}).get("events")
        if not events or events[0]["t"] < t_setup:
            continue
        waits.extend(e["queue_wait_ms"] for e in events
                     if e["kind"] == "admit")
    return stats_mod.percentile(waits, 50.0) if waits else None
