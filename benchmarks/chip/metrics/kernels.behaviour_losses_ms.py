"""Device milliseconds of one run of the train step under
``behaviour_losses``: lambda-returns, moments, actor and critic losses outside the scan."""

from benchmarks.chip.span_reduce import scope_ms


def read(run):
    return scope_ms(run, "behaviour_losses")
