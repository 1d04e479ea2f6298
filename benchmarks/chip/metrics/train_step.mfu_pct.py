"""The whole gradient step's share of the chip's bf16 peak over the window:
FLOPs from shapes (the family's ``train_step_flops``) x the gradient steps the
harness counted in the window / window seconds / the peak of ``peaks.json``."""

from benchmarks.chip.flops import peak_flops_per_s


def read(run):
    steps = run["window"].get("gradient_steps")
    if not steps or run.get("family") is None:
        return None
    flops = run["family"].train_step_flops(run["config"])["total"]
    peak = peak_flops_per_s(run["device"]["kind"]) * run["device"]["count"]
    return 100.0 * flops * steps / run["window"]["seconds"] / peak
