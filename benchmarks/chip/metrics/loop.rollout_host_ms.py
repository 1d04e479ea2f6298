"""Host milliseconds an iteration spends in the ``rollout`` span (player
dispatch, the blocking action fetch, the ring add): the growth of
``sheeprl_phase_seconds_total{phase="rollout"}`` over the window by the env
steps of the window.  The fetch absorbs device time."""


def read(run):
    delta = run["phase_delta_s"].get("rollout")
    steps = run["window"].get("steps")
    return None if delta is None or not steps else 1e3 * delta / steps
