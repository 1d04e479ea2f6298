"""Host milliseconds an update spends in the ``train`` span (the dispatch of
``jit_update``; the wait for it lies under no span, the device being at work).
Growth of ``sheeprl_phase_seconds_total`` by the growth of
``sheeprl_instrumented_calls_total{fn="train_step"}`` between the window's two
scrapes: ``seq.update_dispatch_ms``'s one-line call under this cell's name."""

from benchmarks.chip.lm_reduce import TRAIN_CALLS, counter_rate_ms


def read(run):
    return counter_rate_ms(run, "train", TRAIN_CALLS)
