"""The ``select`` scope's share of its roofline: the least time the chip could
take for the published algorithm's work of one update (the family's
``update_work``: every visible score read once and the selected positions written: bytes alone, the search itself is not counted;
``max(FLOPs / peak, bytes / bandwidth)``, counted from shapes and the positions
the run recorded, the same whatever implements the scope: a floor) over the
scope's device time."""

from benchmarks.chip.lm_reduce import peak, scope_ms


def read(run):
    ms = scope_ms(run, "select")
    family = run.get("family")
    work = family.update_work(run) if ms and hasattr(family, "update_work") else None
    if not work:
        return None
    least_s = max(work["select"]["flops"] / peak(run, "bf16_flops_per_s"), work["select"]["bytes"] / peak(run, "hbm_bytes_per_s"))
    return 100.0 * least_s / (ms / 1e3)
