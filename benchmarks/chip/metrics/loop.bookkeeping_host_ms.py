"""Host milliseconds an env step spends between the env's results and the
checkpoint test (``bookkeeping``: obs copies, reset rows, aggregator, metric
drain).  Growth of ``sheeprl_phase_seconds_total`` by the growth of
``sheeprl_env_steps_total`` between the window's two scrapes."""

from benchmarks.chip.span_reduce import ENV_STEPS, counter_rate_ms


def read(run):
    return counter_rate_ms(run, "bookkeeping", ENV_STEPS)
