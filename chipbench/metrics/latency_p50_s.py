"""latency_p50_s: median over every query answered in the window, from
submit to its result columns on the host (host clock)."""
import numpy as np


def read(run):
    lat = [r.t_done - r.t_submit for r in run.answered]
    return float(np.percentile(lat, 50)) if lat else None
