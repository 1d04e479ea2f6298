"""Host milliseconds a gradient step spends in ``buffer-sample`` + ``train``
(sampling, staging, dispatch): the growth of those phases' seconds over the
window by the gradient steps the harness counted in it."""


def read(run):
    phases = run["phase_delta_s"]
    steps = run["window"].get("gradient_steps")
    if not steps or "train" not in phases:
        return None
    return 1e3 * (phases.get("buffer-sample", 0.0) + phases["train"]) / steps
