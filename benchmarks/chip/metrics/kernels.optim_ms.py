"""Device milliseconds of one run of the train step under
``optim``: clipping, the three optimizer updates and the target critic's."""

from benchmarks.chip.span_reduce import scope_ms


def read(run):
    return scope_ms(run, "optim")
