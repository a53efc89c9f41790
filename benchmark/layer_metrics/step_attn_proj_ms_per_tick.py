"""Device time of the attention block's projections (`mx_qkv`: input norm,
q/k/v products, QK-norm, RoPE or positions; `mx_attn_out`: output gate and
product, post-norm, residual) inside the runs of the decode step program, per
run. The attention call itself is `paged_attn_ms_per_tick`."""
import program_parts


def read(run):
    return program_parts.part_ms_a_run(run, program_parts.STEP,
                                       ("mx_qkv", "mx_attn_out"))
