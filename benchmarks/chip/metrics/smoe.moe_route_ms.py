"""Device milliseconds of one update (``jit_update``) under ``moe_route``:
the router's product over all the model's experts (float32 at highest), softmax, top-8 and gates, the block's second norm.  Self time of every operation by the first scope its path names
(``lm_reduce.py``: the update is scans within scans, so an operation counts
for itself and a ``while`` for nothing but its own overhead)."""

from benchmarks.chip.lm_reduce import scope_ms


def read(run):
    return scope_ms(run, "moe_route")
