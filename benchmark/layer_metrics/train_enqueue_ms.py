"""Median host time of one ``TrainPlane.step`` call, from call to return
(the harness's ``bench.step`` span): what the host spends to enqueue a step."""


def read(run):
    return run.get("span_medians_ms", {}).get("bench.step")
