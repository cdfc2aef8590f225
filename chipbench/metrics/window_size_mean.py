"""window_size_mean: mean ExplainReport.window_size over the queries
answered in the traced window (front end)."""


def read(run):
    sizes = [r.window_size for r in run.answered if r.window_size]
    return sum(sizes) / len(sizes) if sizes else None
