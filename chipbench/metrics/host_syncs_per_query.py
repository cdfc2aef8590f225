"""host_syncs_per_query: the executor's device-to-host reads in the
window (the program's ``exec.host_syncs`` counter: one per read, each
under an ``exec.sync`` span) over the queries answered (executor)."""


def read(run):
    n = len(run.answered)
    if run.counters is None or not n:
        return None
    return run.counters.get("exec.host_syncs", 0) / n
