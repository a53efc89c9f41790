"""Share of the chip's peak arithmetic rate that the prefill attention
kernel (``mx_prefill_attn``: blocked, causal + window, grouped queries)
reaches over the traced prefills. Least time: the operations of the band
over each prompt's REAL tokens (``kv_rows_full`` on the traced
``mx.decode.prefill`` spans; ``flops_moe.band_attention_flops``), every
window layer with its window and every full layer without, over the peak
bf16 rate; kernel time: the summed device time of the ``mx_prefill_attn``
operations in the same trace (it computes the padded rung in float32, so
the share is of what the algorithm needs, not of what the kernel does).
A traced span in which no prompt was prefilled reads nothing (a share of a
peak is never reported as 0)."""
import flops
import flops_moe
import trace_reduce
import trace_within

KERNEL = r"^%?mx_prefill_attn\b"


def read(run):
    cell, trace = run["cell"], run["trace"]
    if trace is None or cell.peaks is None:
        return None
    if not trace_within.span_args(run, ("mx.decode.commit",)):
        return None            # the program writes no such spans
    rows = [r for r in trace_within.span_args(
        run, ("mx.decode.prefill",)) or () if "kv_rows_full" in r]
    seconds, count = trace_reduce.time_matching(trace, KERNEL)
    if not rows or not count:
        return None
    model = cell.config["model"]
    full, window, _e = flops_moe.layer_kinds(model)
    heads, dim = model["num_attention_heads"], model["head_dim"]
    ops = sum(
        full * flops_moe.band_attention_flops(r["kv_rows_full"], heads, dim)
        + window * flops_moe.band_attention_flops(
            r["kv_rows_full"], heads, dim, model["sliding_window"])
        for r in rows)
    return flops.share_of_peak(ops / cell.peaks["bf16_flops_per_s"], seconds,
                               "prefill_attn_roofline")
