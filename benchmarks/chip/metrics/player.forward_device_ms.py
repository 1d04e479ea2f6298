"""Device-busy milliseconds inside one execution of the player's forward pass (the family's ``player`` executable),
from the device trace."""

from benchmarks.chip.span_reduce import module_ms


def read(run):
    return module_ms(run, "player")
