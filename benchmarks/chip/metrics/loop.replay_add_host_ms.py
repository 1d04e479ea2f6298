"""Host milliseconds an env step spends in the replay buffer's ``add``
(``rollout/replay-add``): index math, staging the frame and, with the ring in
HBM, one dispatch.  Growth of ``sheeprl_phase_seconds_total`` by the growth
of ``sheeprl_env_steps_total`` between the window's two scrapes."""

from benchmarks.chip.span_reduce import ENV_STEPS, counter_rate_ms


def read(run):
    return counter_rate_ms(run, "rollout/replay-add", ENV_STEPS)
