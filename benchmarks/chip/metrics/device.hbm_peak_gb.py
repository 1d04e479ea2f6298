"""Peak device memory after the window (``memory_stats()['peak_bytes_in_use']``), in GB."""


def read(run):
    peak = run["device"].get("memory_peak_bytes")
    return None if not peak else peak / 1e9
