"""The decode step's share of its memory roofline: the bytes one step must
move (the family's ``decode_bytes``: the weights held but the embedding's
untouched rows, the linear state read and written; the cache's read left out,
so a floor) over the bandwidth of ``peaks.json``, over its device time."""

from benchmarks.chip.lm_reduce import module_ms, peak


def read(run):
    ms = module_ms(run, "player")
    family = run.get("family")
    if not ms or family is None or not hasattr(family, "decode_bytes"):
        return None
    return 100.0 * family.decode_bytes(run["config"]) / peak(run, "hbm_bytes_per_s") / (ms / 1e3)
