"""Share of the traced span in which no operation ran on the device."""


def read(run):
    trace = run.get("trace")
    return None if not trace else trace.get("idle_pct")
