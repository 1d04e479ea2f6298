"""Host milliseconds a gradient step spends in the ``train`` span (the step's
dispatch).  Growth of ``sheeprl_phase_seconds_total`` by the growth of
``sheeprl_instrumented_calls_total{fn="train_step"}``, the program's own
count of the step's calls, between the window's two scrapes."""

from benchmarks.chip.span_reduce import TRAIN_CALLS, counter_rate_ms


def read(run):
    return counter_rate_ms(run, "train", TRAIN_CALLS)
