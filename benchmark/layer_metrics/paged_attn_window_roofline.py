"""Share of its memory roofline that the paged-attention kernel reaches in
the decode step of a model with window and full layers. Least time: the K
and V rows a tick has to read — every live token in each full layer, at most
the window in each window layer (``kv_rows_full``, ``kv_rows_window`` on the
traced ``mx.decode.commit`` spans; ``flops_moe.grouped_decode_kv_bytes``) —
over the peak HBM bandwidth; kernel time: the ``mx_paged_attn`` operations
inside the traced runs of the decode step.

The count stays the benchmark's: the driver's own ``traced_kv_token_reads``
(from the request traces: live tokens of the ticks between the instants the
profiler was started and stopped, a stretch that CONTAINS the traced ticks —
it also holds the ticks that ran while the profiler wrote its file) bounds
the spans' ``kv_rows_full`` from above; spans that claim over 2 % more give
no number, with the reason printed."""
import json

import flops
import flops_moe
import trace_within

KERNEL = r"^%?mx_paged_attn\b"
STEP = "mx_decode_step"


def read(run):
    cell, trace = run["cell"], run["trace"]
    ticks = trace_within.span_args(run, ("mx.decode.commit",))
    if trace is None or not ticks or cell.peaks is None:
        return None
    ticks = [t for t in ticks if "kv_rows_full" in t]
    seconds, count, _steps = trace_within.time_within(trace, KERNEL, STEP)
    if not ticks or not count:
        return None
    rows_full = sum(t["kv_rows_full"] for t in ticks)
    rows_window = sum(t["kv_rows_window"] for t in ticks)
    driver = run["counters"].get("traced_kv_token_reads")
    print(json.dumps({"phase": "paged_attn_window_roofline",
                      "ticks": len(ticks), "kv_rows_full": rows_full,
                      "kv_rows_window": rows_window,
                      "traced_kv_token_reads": driver}), flush=True)
    if not driver or rows_full > 1.02 * driver:
        print(json.dumps({
            "phase": "paged_attn_window_roofline", "given": None,
            "reason": "the spans' kv_rows_full lie over the driver's "
            "traced_kv_token_reads by more than 2 %"}), flush=True)
        return None
    model = cell.config["model"]
    full, window, _e = flops_moe.layer_kinds(model)
    itemsize = {"float32": 4, "bfloat16": 2}[cell.config["kv_dtype"]]
    least = flops_moe.grouped_decode_kv_bytes(
        rows_full, rows_window, full, window, model["num_key_value_heads"],
        model["head_dim"], itemsize) / cell.peaks["hbm_bytes_per_s"]
    return flops.share_of_peak(least, seconds, "paged_attn_window_roofline")
