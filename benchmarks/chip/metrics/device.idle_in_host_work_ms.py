"""Idle device milliseconds an iteration under every ``sheeprl/...`` span but
the action fetch: the host holding the chip back."""

from benchmarks.chip.span_reduce import FETCH_SPAN, UNATTRIBUTED, idle_ms


def read(run):
    idle = idle_ms(run)
    if idle is None:
        return None
    return sum(ms for span, ms in idle.items() if span not in (UNATTRIBUTED, FETCH_SPAN))
