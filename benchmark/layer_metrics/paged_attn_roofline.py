"""Share of its memory roofline that the paged-attention kernel reaches in
the decode step. Least time: the K and V rows of every live token in every
layer, once per tick (``benchmark/flops.py``, float32 pool), over the peak
HBM bandwidth of ``benchmark/peaks.json``; the ticks are those the request
traces place inside the traced span. Kernel time: the summed device time of
the ``tpu_custom_call`` operations of the decode step in the same span — the
step holds no other custom call today (the kernel has no ``name`` yet).

The span's end is the instant before the profiler was told to stop (PR 44), so
the ticks counted are the ticks traced; the reader prints both counts — the
request traces' ticks inside the span and the summed ``active`` of the traced
``mx.decode.tick`` spans — so that a run shows they agree."""
import json

import flops
import trace_reduce
import trace_within

KERNEL = r"custom-call\("


def read(run):
    cell, trace = run["cell"], run["trace"]
    reads = run["counters"].get("traced_kv_token_reads")
    if trace is None or not reads or cell.peaks is None:
        return None
    seconds, count = trace_reduce.time_matching(trace, KERNEL)
    if not count:
        return None
    in_trace = [t["active"] for t in trace_within.span_args(
        run, ("mx.decode.tick",)) or () if t.get("active")]
    print(json.dumps({
        "phase": "paged_attn_roofline", "traced_kv_token_reads": reads,
        "slot_ticks_by_request_traces":
            run["counters"].get("traced_slot_ticks"),
        "slot_ticks_by_trace_spans": sum(in_trace),
        "steps_in_trace": len(in_trace), "kernel_events": count}), flush=True)
    model = cell.config["model"]
    itemsize = {"float32": 4, "bfloat16": 2}[cell.config["kv_dtype"]]
    least = flops.paged_decode_kv_bytes(
        reads, model["num_layers"], model["num_heads"], model["head_dim"],
        itemsize) / cell.peaks["hbm_bytes_per_s"]
    return flops.share_of_peak(least, seconds, "paged_attn_roofline")
