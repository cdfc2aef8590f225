"""device_idle_share: 1 minus the device-busy union over the traced
window (device)."""


def read(run):
    if run.trace is None:
        return None
    return 1.0 - run.trace["busy_s"] / run.trace["window_s"]
