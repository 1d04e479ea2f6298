"""Device milliseconds of one ``jit_train_step`` run under
no scope: batch staging, metrics, health statistics, the sentinel's select."""

from benchmarks.chip.span_reduce import scope_ms


def read(run):
    return scope_ms(run, "unscoped")
