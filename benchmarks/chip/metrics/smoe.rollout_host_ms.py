"""Host milliseconds a vector step spends in the ``rollout`` span (staging, the player's dispatch, the blocking action fetch, the env step, the buffer add).
Growth of ``sheeprl_phase_seconds_total`` by the growth of
``sheeprl_phase_calls_total{phase="rollout/action-fetch"}``, the program's own count of
vector steps, between the window's two scrapes: ``seq.rollout_host_ms``'s one-line call under this cell's name."""

from benchmarks.chip.lm_reduce import counter_rate_ms


def read(run):
    return counter_rate_ms(run, "rollout", 'sheeprl_phase_calls_total{phase="rollout/action-fetch"}')
