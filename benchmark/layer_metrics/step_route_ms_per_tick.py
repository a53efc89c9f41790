"""Device time of what an expert layer does around its products
(`mx_moe_route`: the norm in front, scores, top-k, the sort into groups;
`mx_moe_combine`: un-permute, weights, sum, the norm behind, residual) inside
the runs of the decode step program, per run. The products themselves are
`moe_ms_per_tick`."""
import program_parts


def read(run):
    return program_parts.part_ms_a_run(run, program_parts.STEP,
                                       ("mx_moe_route", "mx_moe_combine"))
