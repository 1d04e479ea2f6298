"""Idle device milliseconds an iteration while the host is inside
``sheeprl/rollout/action-fetch``: the host waiting on the device, a bubble of
the pipeline and not host work."""

from benchmarks.chip.span_reduce import FETCH_SPAN, idle_ms


def read(run):
    idle = idle_ms(run)
    return None if idle is None else idle.get(FETCH_SPAN, 0.0)
