"""The ``moe_experts`` scope's share of its roofline: the least time the chip could
take for the published algorithm's work of one update (the family's
``update_work``: the three products of every pick that fell on a held expert, three times with the backward;
``max(FLOPs / peak, bytes / bandwidth)``, counted from shapes and the positions
the run recorded, the same whatever implements the scope: a floor) over the
scope's device time."""

from benchmarks.chip.lm_reduce import peak, scope_ms


def read(run):
    ms = scope_ms(run, "moe_experts")
    family = run.get("family")
    work = family.update_work(run) if ms and hasattr(family, "update_work") else None
    if not work:
        return None
    least_s = max(work["moe_experts"]["flops"] / peak(run, "bf16_flops_per_s"), work["moe_experts"]["bytes"] / peak(run, "hbm_bytes_per_s"))
    return 100.0 * least_s / (ms / 1e3)
