"""Device time of the dense MLPs (`mx_mlp`: norm, products, activation,
residual) inside the runs of the decode step program, per run."""
import program_parts


def read(run):
    return program_parts.part_ms_a_run(run, program_parts.STEP, ("mx_mlp",))
