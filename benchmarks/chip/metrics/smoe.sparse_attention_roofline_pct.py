"""The ``sparse_attention`` scope's share of its roofline: the least time the chip could
take for the published algorithm's work of one update (the family's
``update_work``: ``4 Hq dh`` FLOPs an attended position a query, three times with the backward;
``max(FLOPs / peak, bytes / bandwidth)``, counted from shapes and the positions
the run recorded, the same whatever implements the scope: a floor) over the
scope's device time."""

from benchmarks.chip.lm_reduce import peak, scope_ms


def read(run):
    ms = scope_ms(run, "sparse_attention")
    family = run.get("family")
    work = family.update_work(run) if ms and hasattr(family, "update_work") else None
    if not work:
        return None
    least_s = max(work["sparse_attention"]["flops"] / peak(run, "bf16_flops_per_s"), work["sparse_attention"]["bytes"] / peak(run, "hbm_bytes_per_s"))
    return 100.0 * least_s / (ms / 1e3)
