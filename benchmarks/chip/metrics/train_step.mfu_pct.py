"""The whole gradient step's share of the chip's bf16 peak over the window:
FLOPs from shapes (``flops.py``) x the gradient steps the harness counted in
the window / window seconds / the peak of ``peaks.json``."""

from benchmarks.chip.flops import peak_flops_per_s, train_step_flops


def read(run):
    steps = run["window"].get("gradient_steps")
    if not steps:
        return None
    flops = train_step_flops(run["config"]["shapes"])["total"]
    peak = peak_flops_per_s(run["device"]["kind"]) * run["device"]["count"]
    return 100.0 * flops * steps / run["window"]["seconds"] / peak
