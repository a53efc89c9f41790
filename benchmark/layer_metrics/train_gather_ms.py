"""Median of ``mx.train.gather`` in the traced window: the layout check of
every parameter and state leaf, the donation bookkeeping, the jit key and
its lookup."""
import program_spans


def read(run):
    return program_spans.span_stat(run, "mx.train.gather")
