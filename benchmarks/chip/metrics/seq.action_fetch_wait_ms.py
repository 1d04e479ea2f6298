"""Host milliseconds a vector step spends in the blocking action fetch (``rollout/action-fetch``): the host waiting for the decode step.
Growth of ``sheeprl_phase_seconds_total`` by the growth of
``sheeprl_phase_calls_total{phase="rollout/action-fetch"}``, the program's own count of
vector steps, between the window's two scrapes."""

from benchmarks.chip.lm_reduce import counter_rate_ms


def read(run):
    return counter_rate_ms(run, "rollout/action-fetch", 'sheeprl_phase_calls_total{phase="rollout/action-fetch"}')
