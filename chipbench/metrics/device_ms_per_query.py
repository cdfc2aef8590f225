"""device_ms_per_query: the union of device-busy intervals in the traced
window, in milliseconds, over the queries answered in it (executor)."""


def read(run):
    n = len(run.answered)
    if run.trace is None or not n:
        return None
    return run.trace["busy_s"] * 1e3 / n
