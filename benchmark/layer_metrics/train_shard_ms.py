"""Median of ``mx.train.shard`` in the traced window: the two
``parallel.shard_to_mesh`` calls that lay data and label over the mesh."""
import program_spans


def read(run):
    return program_spans.span_stat(run, "mx.train.shard")
