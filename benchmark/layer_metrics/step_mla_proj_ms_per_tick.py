"""Device time of the latent layers' projections (`mx_mla_proj`: input norm,
W_q, W_kva, the latent norm, RoPE, the two absorption products, the gate,
W_o and residual) inside the runs of the decode step program, per run. The
attention launch itself is under `mx_attn` (`mla_attn_roofline`)."""
import program_parts


def read(run):
    return program_parts.part_ms_a_run(run, program_parts.STEP,
                                       ("mx_mla_proj",))
