"""Share of the prefill runs' device time under the expert layer's parts
(`mx_moe_route`, `mx_moe_experts`, `mx_moe_shared`, `mx_moe_combine`)."""
import program_parts


def read(run):
    return program_parts.part_share_pct(run, program_parts.PREFILL, "mx_moe_")
