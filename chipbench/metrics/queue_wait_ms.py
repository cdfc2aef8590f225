"""queue_wait_ms: milliseconds from a query's submit to the start of its
window's work, over the queries of the traced window (front end).  The
wait is the window filling, then the window queueing behind earlier ones
in the async front.

A window's work starts where the program's ``repro.window`` annotation
opens.  The async front closes windows in submit order and runs them one
after another, so the queries, taken in submit order, fill the program
windows in turn, each with as many queries as their ``window_size``
says.  Submit times are on the harness's clock; the trace's times are
put on it by the ``bench.window`` annotation, which opens at
``run.t_start``.  A run whose queries and windows do not match up that
way (a failed query, a program without the annotation) reads nothing."""
from chipbench import tracereduce

PROGRAM_WINDOW = "repro.window"


def window_starts(record):
    """The program windows' start times inside the traced window, on the
    harness's clock less ``run.t_start``, in seconds; None without a
    traced window."""
    win = tracereduce.window_of(record)
    if win is None:
        return None
    lo, hi = win
    return sorted((e["ts"] - lo) * 1e-9 for e in record["host"]
                  if e["name"] == PROGRAM_WINDOW and lo <= e["ts"] <= hi)


def read(run):
    if run.trace_record is None:
        return None
    starts = window_starts(run.trace_record)
    queries = sorted(run.records, key=lambda r: r.t_submit)
    if not starts or not queries:
        return None
    total, i = 0.0, 0
    for start in starts:
        n = queries[i].window_size if i < len(queries) else 0
        window = queries[i:i + n]
        if not n or len(window) < n or any(r.window_size != n
                                           for r in window):
            return None
        t = run.t_start + start
        total += sum(t - r.t_submit for r in window)
        i += n
    if i != len(queries):
        return None
    return total * 1e3 / len(queries)
