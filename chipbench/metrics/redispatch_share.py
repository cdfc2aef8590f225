"""redispatch_share: the executor's deferred dispatches that ran a second
time, at the exact size, because the cost model's estimate was too small
or more than twice too large (``exec.redispatches`` over
``exec.deferred_dispatches``, the program's counters), in the window;
None with no deferred dispatch (executor)."""


def read(run):
    if run.counters is None:
        return None
    deferred = run.counters.get("exec.deferred_dispatches", 0)
    if not deferred:
        return None
    return run.counters.get("exec.redispatches", 0) / deferred
