"""window_idle_ms_per_query: milliseconds the device is idle while the
program works on a window, over the queries answered in the traced
window (device).

Busy is the union of each device plane's operations inside the
harness's ``bench.window``, as ``device_idle_share`` reads it.  The idle
rest is split by the program's ``repro.window`` annotations: idle inside
them is time the host held the chip while it planned, read counts or
resolved a window; idle outside them is the time between windows
(handoff to the clients, their fetches and resubmits).  Both are
averaged over the device planes."""
from collections import defaultdict

from chipbench import tracereduce

PROGRAM_WINDOW = "repro.window"


def _clipped(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def _overlap(xs, ys) -> float:
    """Length of the intersection of two sorted, disjoint interval
    lists."""
    total, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def split_idle(record):
    """(idle inside ``repro.window``, idle outside it), in ns, averaged
    over device planes; None when the trace holds no window, no program
    window or no device operation in the window."""
    win = tracereduce.window_of(record)
    if win is None:
        return None
    lo, hi = win
    windows = tracereduce._union(_clipped(
        [(e["ts"], e["ts"] + e["dur"]) for e in record["host"]
         if e["name"] == PROGRAM_WINDOW], lo, hi))
    by_plane = defaultdict(list)
    for e in record["device"]:
        by_plane[e["plane"]].append((e["ts"], e["ts"] + e["dur"]))
    busy = [tracereduce._union(_clipped(iv, lo, hi))
            for iv in by_plane.values()]
    busy = [b for b in busy if b]
    if not windows or not busy:
        return None
    in_windows = sum(b - a for a, b in windows)
    inside = outside = 0.0
    for b in busy:
        busy_in = _overlap(b, windows)
        inside += in_windows - busy_in
        outside += (hi - lo - in_windows) - (sum(y - x for x, y in b)
                                             - busy_in)
    return inside / len(busy), outside / len(busy)


def read(run):
    n = len(run.answered)
    if run.trace_record is None or not n:
        return None
    split = split_idle(run.trace_record)
    if split is None:
        return None
    return split[0] * 1e-6 / n
