"""Device-busy milliseconds inside one execution of the HBM ring's sample (the family's ``replay_gather`` executable),
from the device trace."""

from benchmarks.chip.span_reduce import module_ms


def read(run):
    return module_ms(run, "replay_gather")
