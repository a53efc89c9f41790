"""Share of its roofline that the grouped product (``mx_moe_gmm``) reaches
over the traced decode ticks and prefills. Least time: from the rows routed
to held experts and the distinct held experts hit that the program's spans
report for each program run (``moe_rows_held``, ``moe_experts_hit`` on
``mx.decode.commit`` and ``mx.decode.prefill``), by
``flops_moe.grouped_swiglu_least_seconds``; kernel time: the summed device
time of the ``mx_moe_gmm`` operations in the same trace."""
import flops
import flops_moe
import trace_reduce
import trace_within

KERNEL = r"^%?mx_moe_gmm\b"


def read(run):
    cell, trace = run["cell"], run["trace"]
    rows = trace_within.span_args(run, ("mx.decode.commit",
                                        "mx.decode.prefill"))
    if trace is None or not rows or cell.peaks is None:
        return None
    rows = [r for r in rows if "moe_rows_held" in r]
    seconds, count = trace_reduce.time_matching(trace, KERNEL)
    if not rows or not count:
        return None
    model = cell.config["model"]
    least = sum(flops_moe.grouped_swiglu_least_seconds(
        r["moe_rows_held"], r["moe_experts_hit"], model["hidden_size"],
        model["moe_intermediate_size"], cell.peaks)[0] for r in rows)
    return flops.share_of_peak(least, seconds, "moe_gmm_roofline")
