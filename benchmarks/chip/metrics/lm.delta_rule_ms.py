"""Device milliseconds of one update (``jit_update``) under ``delta_rule``:
the gated delta rule itself (chunked form, its triangular solve and scan), forward, recomputation and backward.  Self time of every operation by the first scope its path names
(``lm_reduce.py``: the update is scans within scans, so an operation counts
for itself and a ``while`` for nothing but its own overhead)."""

from benchmarks.chip.lm_reduce import scope_ms


def read(run):
    return scope_ms(run, "delta_rule")
