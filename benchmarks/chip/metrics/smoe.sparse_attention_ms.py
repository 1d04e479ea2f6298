"""Device milliseconds of one update (``jit_update``) under ``sparse_attention``:
the main attention over the selection: the masked products over cache and sequence, the softmax, the weighted values.  Self time of every operation by the first scope its path names
(``lm_reduce.py``: the update is scans within scans, so an operation counts
for itself and a ``while`` for nothing but its own overhead)."""

from benchmarks.chip.lm_reduce import scope_ms


def read(run):
    return scope_ms(run, "sparse_attention")
