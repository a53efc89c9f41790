"""Median device duration of the runs of the prefill program
(`jit_mx_prefill`, whatever the rung) in the traced window."""
import program_parts


def read(run):
    return program_parts.run_ms_p50(run, program_parts.PREFILL)
