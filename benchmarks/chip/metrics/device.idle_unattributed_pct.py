"""Share of the device's idle time, first ``sheeprl/rollout`` start to last,
that lies under no span of the program."""

from benchmarks.chip.span_reduce import UNATTRIBUTED, idle_ms


def read(run):
    idle = idle_ms(run)
    if idle is None or not sum(idle.values()):
        return None
    return 100.0 * idle.get(UNATTRIBUTED, 0.0) / sum(idle.values())
