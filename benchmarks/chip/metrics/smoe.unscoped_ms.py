"""Device milliseconds of one update (``jit_update``) whose operations name none
of the family's scopes: loop overhead of the scans, the minibatch's gather,
copies (``lm_reduce.py``)."""

from benchmarks.chip.lm_reduce import UNSCOPED, scope_ms


def read(run):
    return scope_ms(run, UNSCOPED)
