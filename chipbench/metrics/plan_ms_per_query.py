"""plan_ms_per_query: milliseconds in the program's canonicalize and mqo
spans (host-only planning), over the queries answered in the window."""


def read(run):
    n = len(run.answered)
    if run.plan_spans_s is None or not n:
        return None
    return run.plan_spans_s * 1e3 / n
