"""Device milliseconds of one run of the train step under
``encoder``: the conv and MLP encoders, forward and backward."""

from benchmarks.chip.span_reduce import scope_ms


def read(run):
    return scope_ms(run, "encoder")
