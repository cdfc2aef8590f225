"""qps: queries answered in the window over the time from the window's
start to the last answer (host clock)."""


def read(run):
    n = len(run.answered)
    if not n:
        return None
    return n / (run.t_last - run.t_start)
