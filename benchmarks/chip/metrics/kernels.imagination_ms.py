"""Device milliseconds of one run of the train step under
``imagination``: the horizon scan inside the actor loss."""

from benchmarks.chip.span_reduce import scope_ms


def read(run):
    return scope_ms(run, "imagination")
