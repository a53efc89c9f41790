"""Device time of the delta-rule recurrence (`mx_kda_state`: the one-token
update of every kda layer's per-slot state, the output norm and gate) inside
the runs of the decode step program, per run."""
import program_parts


def read(run):
    return program_parts.part_ms_a_run(run, program_parts.STEP,
                                       ("mx_kda_state",))
