"""Seconds from process start to the first train step's dispatch (journal
``telemetry_cost.t`` of ``train_step``; on a warm start the harness's own
stamp of the first call into the step).  Host clock."""


def read(run):
    first = run.get("first_train_step_t")
    return None if first is None else first - run["t_start"]
