"""Median over the traced steps of ``mx.train.step`` minus its children:
the host time of a step that no child span names yet."""
import program_spans


def read(run):
    return program_spans.span_stat(run, "mx.train.step", "self_median_ms")
