"""Host milliseconds an env step spends in the blocking action fetch
(``rollout/action-fetch``): the host waiting for the device, not working.
Growth of ``sheeprl_phase_seconds_total`` by the growth of
``sheeprl_env_steps_total`` between the window's two scrapes."""

from benchmarks.chip.span_reduce import ENV_STEPS, counter_rate_ms


def read(run):
    return counter_rate_ms(run, "rollout/action-fetch", ENV_STEPS)
