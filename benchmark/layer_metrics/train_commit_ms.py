"""Median of ``mx.train.commit`` (the write-back of the step's outputs onto
parameters and optimizer state, the invalidation of donated buffers) plus
the median of ``mx.train.hbm_sample`` (``memory_stats()`` of every local
device) in the traced window: what a step does after its dispatch."""
import program_spans


def read(run):
    commit = program_spans.span_stat(run, "mx.train.commit")
    if commit is None:
        return None
    return commit + (program_spans.span_stat(run, "mx.train.hbm_sample")
                     or 0.0)
