"""The decode step's share of its memory roofline: the bytes one step must
move (the family's ``decode_bytes``: the view's kernels at the width the view
holds them, the index keys of the positions held, the selected rows of keys
and values, the rows written, counted from shapes and the positions the run
recorded: a floor) over the bandwidth of ``peaks.json``, over its device time."""

from benchmarks.chip.lm_reduce import module_ms, peak


def read(run):
    ms = module_ms(run, "player")
    family = run.get("family")
    nbytes = family.decode_bytes(run) if ms and hasattr(family, "decode_bytes") else None
    if not nbytes:
        return None
    return 100.0 * nbytes / peak(run, "hbm_bytes_per_s") / (ms / 1e3)
