"""latency_p95_s: 95th percentile over every query answered in the
window, from submit to its result columns on the host (host clock)."""
import numpy as np


def read(run):
    lat = [r.t_done - r.t_submit for r in run.answered]
    return float(np.percentile(lat, 95)) if lat else None
