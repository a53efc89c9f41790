"""Peak of the window group's pages in use over its allocatable pages, over
the traced decode ticks and prefills (``kv_window_pages``,
``kv_window_capacity`` on ``mx.decode.commit`` and ``mx.decode.prefill``).
``kv_pages_peak_pct`` is the same for the full group, over the whole
window."""
import trace_within


def read(run):
    rows = trace_within.span_args(run, ("mx.decode.commit",
                                        "mx.decode.prefill"))
    rows = [r for r in rows or () if r.get("kv_window_capacity")]
    if not rows:
        return None
    return 100.0 * max(r["kv_window_pages"] for r in rows) \
        / rows[0]["kv_window_capacity"]
