"""ce_reuse_share: answered queries that read a resident covering
expression (resident_reuse) or resumed from one by subsumption
(subsumption_hit), over the queries answered (memory hierarchy)."""


def read(run):
    answered = [r for r in run.answered if r.window_size]
    if not answered:
        return None
    return sum(r.reuse for r in answered) / len(answered)
