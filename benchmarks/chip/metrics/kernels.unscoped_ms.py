"""Device milliseconds of one run of the train step under
no scope: batch staging, metrics, health statistics, the sentinel's select."""

from benchmarks.chip.span_reduce import scope_ms


def read(run):
    return scope_ms(run, "unscoped")
