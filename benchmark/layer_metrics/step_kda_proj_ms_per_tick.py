"""Device time of the kda layers' projections (`mx_kda_proj`: input norm,
the q/k/v, decay and beta products, the short convolution, the output
product and residual) inside the runs of the decode step program, per run.
The recurrence itself is `kda_state_ms_per_tick`."""
import program_parts


def read(run):
    return program_parts.part_ms_a_run(run, program_parts.STEP,
                                       ("mx_kda_proj",))
