"""Device milliseconds of one update (``jit_update``) under ``optim``:
the gradient's norms, the clip and AdamW over every leaf.  Self time of every operation by the first scope its path names
(``lm_reduce.py``: the update is scans within scans, so an operation counts
for itself and a ``while`` for nothing but its own overhead)."""

from benchmarks.chip.lm_reduce import scope_ms


def read(run):
    return scope_ms(run, "optim")
