"""Share of the decode step's device time (its operations inside the runs of
the step program) that the map of the program's parts puts under no part:
over 10, a scope is missing or the map is stale."""
import program_parts


def read(run):
    return program_parts.unnamed_pct(run, program_parts.STEP)
