"""Total ``mx.decode.prefill`` over total ``mx.decode.tick`` in the traced
window: prefill's share of the worker's time."""
import program_spans


def read(run):
    tick = program_spans.span_stat(run, "mx.decode.tick", "total_ms")
    if not tick:
        return None
    prefill = program_spans.span_stat(run, "mx.decode.prefill", "total_ms")
    return 100.0 * (prefill or 0.0) / tick
