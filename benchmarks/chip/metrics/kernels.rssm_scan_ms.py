"""Device milliseconds of one run of the train step under
``rssm_scan``: the dynamic-learning scan over the sequence, forward and backward."""

from benchmarks.chip.span_reduce import scope_ms


def read(run):
    return scope_ms(run, "rssm_scan")
