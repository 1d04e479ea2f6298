"""Device milliseconds of one run of the train step under
``decoder_heads``: decoder, reward and continue heads and the world-model loss, forward and backward."""

from benchmarks.chip.span_reduce import scope_ms


def read(run):
    return scope_ms(run, "decoder_heads")
