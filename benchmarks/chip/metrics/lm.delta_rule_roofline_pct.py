"""The ``delta_rule`` scope's share of its roofline: the least time the chip
could take for the *recurrent form's* work of one update (the family's
``delta_rule_work``: ``max(FLOPs / peak, bytes / bandwidth)``, counted from
shapes, the same whatever implements the scope) over the scope's device time."""

from benchmarks.chip.lm_reduce import peak, scope_ms


def read(run):
    ms = scope_ms(run, "delta_rule")
    family = run.get("family")
    if not ms or family is None or not hasattr(family, "delta_rule_work"):
        return None
    work = family.delta_rule_work(run["config"])
    least_s = max(work["flops"] / peak(run, "bf16_flops_per_s"), work["bytes"] / peak(run, "hbm_bytes_per_s"))
    return 100.0 * least_s / (ms / 1e3)
