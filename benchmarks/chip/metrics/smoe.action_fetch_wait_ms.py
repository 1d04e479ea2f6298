"""Host milliseconds a vector step waits in the blocking fetch of the sampled tokens (``rollout/action-fetch``): the decode step's device time and its launch.
Growth of ``sheeprl_phase_seconds_total`` by the growth of
``sheeprl_phase_calls_total{phase="rollout/action-fetch"}``, the program's own count of
vector steps, between the window's two scrapes: ``seq.action_fetch_wait_ms``'s one-line call under this cell's name."""

from benchmarks.chip.lm_reduce import counter_rate_ms


def read(run):
    return counter_rate_ms(run, "rollout/action-fetch", 'sheeprl_phase_calls_total{phase="rollout/action-fetch"}')
