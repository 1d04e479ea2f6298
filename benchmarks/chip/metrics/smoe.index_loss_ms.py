"""Device milliseconds of one update (``jit_update``) under ``index_loss``:
the indexers' loss: the heads' weights summed, the softmax of the scores over the selection, the KL.  Self time of every operation by the first scope its path names
(``lm_reduce.py``: the update is scans within scans, so an operation counts
for itself and a ``while`` for nothing but its own overhead)."""

from benchmarks.chip.lm_reduce import scope_ms


def read(run):
    return scope_ms(run, "index_loss")
