"""Device milliseconds of one update (``jit_update``) under ``attn_proj``:
the q, k, v and o projections, the head norms of q and k and the rotary phases, the block's first norm.  Self time of every operation by the first scope its path names
(``lm_reduce.py``: the update is scans within scans, so an operation counts
for itself and a ``while`` for nothing but its own overhead)."""

from benchmarks.chip.lm_reduce import scope_ms


def read(run):
    return scope_ms(run, "attn_proj")
