"""Share of its memory roofline that the paged-attention kernel reaches in
the decode step. Least time: the K and V rows of every live token in every
layer, once per tick (``benchmark/flops.py``, float32 pool), over the peak
HBM bandwidth of ``benchmark/peaks.json``; the ticks are those the request
traces place inside the traced span. Kernel time: the summed device time of
the ``tpu_custom_call`` operations of the decode step in the same span — the
step holds no other custom call today (the kernel has no ``name`` yet)."""
import flops
import trace_reduce

KERNEL = r"custom-call\("


def read(run):
    cell, trace = run["cell"], run["trace"]
    reads = run["counters"].get("traced_kv_token_reads")
    if trace is None or not reads or cell.peaks is None:
        return None
    seconds, count = trace_reduce.time_matching(trace, KERNEL)
    if not count:
        return None
    model = cell.config["model"]
    itemsize = {"float32": 4, "bfloat16": 2}[cell.config["kv_dtype"]]
    least = flops.paged_decode_kv_bytes(
        reads, model["num_layers"], model["num_heads"], model["head_dim"],
        itemsize) / cell.peaks["hbm_bytes_per_s"]
    return flops.share_of_peak(least, seconds, "paged_attn_roofline")
