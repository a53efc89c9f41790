"""Median of ``mx.train.dispatch`` in the traced window: the
``telemetry.jit_call("trainplane.step", ...)`` alone — flattening the
operands and the runtime's enqueue."""
import program_spans


def read(run):
    return program_spans.span_stat(run, "mx.train.dispatch")
