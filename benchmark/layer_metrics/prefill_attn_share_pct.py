"""Share of the prefill runs' device time (their operations' time) under
`mx_attn`: the attention call over the prompt (`_band_kernel` or the dense
causal product, whichever the model uses)."""
import program_parts


def read(run):
    return program_parts.part_share_pct(run, program_parts.PREFILL,
                                        ("mx_attn",))
