"""Share of its memory roofline that the latent decode attention
(``mx_mla_attn``) reaches. Least time: every live token's latent row in every
latent layer, once a tick (``latent_rows_read`` on the traced
``mx.decode.commit`` spans; ``flops_ling.latent_least_seconds``, float32
pool) over the peak HBM bandwidth; kernel time: the summed device time of
the ``mx_mla_attn`` operations inside the runs of the decode step."""
import flops
import flops_ling
import trace_within

KERNEL = r"^%?mx_mla_attn\b"
STEP = "mx_decode_step"


def read(run):
    cell, trace = run["cell"], run["trace"]
    ticks = [t for t in trace_within.span_args(run, ("mx.decode.commit",))
             or () if t.get("latent_rows_read")]
    if trace is None or not ticks or cell.peaks is None:
        return None
    seconds, count, _steps = trace_within.time_within(trace, KERNEL, STEP)
    if not count:
        return None
    itemsize = {"float32": 4, "bfloat16": 2}[cell.config["kv_dtype"]]
    least = sum(flops_ling.latent_least_seconds(
        t["latent_rows_read"], cell.config["model"], itemsize, cell.peaks)
        for t in ticks)
    return flops.share_of_peak(least, seconds, "mla_attn_roofline")
