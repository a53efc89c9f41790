"""Median of ``mx.train.prologue`` in the traced window: per trainable row
the update count and the host scalars (three float32 scalar puts a row),
state creation for rows added late, the ZeRO plane's acquire."""
import program_spans


def read(run):
    return program_spans.span_stat(run, "mx.train.prologue")
