"""Rows of the busiest held expert of a decode tick over the mean of the
held experts (``moe_load_max`` against ``moe_rows_held`` over expert layers
x experts held, from the traced ``mx.decode.commit`` spans): the median over
the traced ticks that routed a row here. 1.0 is an even load."""
import statistics

import flops_moe
import trace_within


def read(run):
    ticks = trace_within.span_args(run, ("mx.decode.commit",))
    if not ticks:
        return None
    model = run["cell"].config["model"]
    cells = flops_moe.layer_kinds(model)[2] * model["held_experts"][1]
    ratios = [t["moe_load_max"] * cells / t["moe_rows_held"] for t in ticks
              if t.get("moe_rows_held")]
    return statistics.median(ratios) if ratios else None
