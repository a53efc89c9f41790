"""Median, over the traced worker passes that ran a decode step, of
``mx.decode.tick`` minus its ``mx.decode.fetch``: the host work of a tick
that is not the wait for the device."""
import statistics

import program_spans


def read(run):
    got = program_spans.load(run)
    if not got:
        return None
    host = []
    for tick, kids in program_spans.children_of(got, "mx.decode.tick"):
        fetch = [k for k in kids if k.name == "mx.decode.fetch"]
        if fetch:
            host.append(((tick.end - tick.start)
                         - sum(k.end - k.start for k in fetch)) / 1e6)
    return statistics.median(host) if host else None
