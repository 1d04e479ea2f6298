"""Device milliseconds of one ``jit_train_step`` run under
``encoder``: the conv and MLP encoders, forward and backward."""

from benchmarks.chip.span_reduce import scope_ms


def read(run):
    return scope_ms(run, "encoder")
