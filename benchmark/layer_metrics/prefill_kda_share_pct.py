"""Share of the prefill runs' device time (their operations' time) under the
kda layers' parts: `mx_kda_state` (the chunked scan) and `mx_kda_proj`."""
import program_parts


def read(run):
    return program_parts.part_share_pct(run, program_parts.PREFILL,
                                        "mx_kda_")
