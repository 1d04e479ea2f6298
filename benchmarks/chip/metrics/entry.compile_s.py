"""Backend compile seconds of the whole run (journal ``telemetry_summary.compile_time_s``)."""


def read(run):
    summary = run["journal"].get("telemetry_summary")
    return None if not summary else summary[-1].get("compile_time_s")
