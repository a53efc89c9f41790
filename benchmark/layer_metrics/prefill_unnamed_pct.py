"""Share of the prefill runs' device time that the maps of the prefill
programs' parts put under no part: over 10, a scope is missing or a map is
stale."""
import program_parts


def read(run):
    return program_parts.unnamed_pct(run, program_parts.PREFILL)
