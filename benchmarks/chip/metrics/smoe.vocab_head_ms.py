"""Device milliseconds of one update (``jit_update``) under ``vocab_head``:
the final norm, the head over the ids held and the value read-out.  Self time of every operation by the first scope its path names
(``lm_reduce.py``: the update is scans within scans, so an operation counts
for itself and a ``while`` for nothing but its own overhead)."""

from benchmarks.chip.lm_reduce import scope_ms


def read(run):
    return scope_ms(run, "vocab_head")
