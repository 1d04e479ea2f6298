"""Device milliseconds of one ``jit_train_step`` run under
``rssm_scan``: the dynamic-learning scan over the sequence, forward and backward."""

from benchmarks.chip.span_reduce import scope_ms


def read(run):
    return scope_ms(run, "rssm_scan")
