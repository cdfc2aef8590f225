"""The comparison that decides ``correct``.

Every answer of the measured window is compared with the plain reference
(``chipbench/reference.py``) once the window has closed.  Three numbers
are compared, each with its limit:

* ``mismatched_answers``: answers whose exact parts differ from the
  reference: column names, row count, group keys, counts, integer sums,
  selected rows, the sorted rows of a sort/limit.  An exact comparison:
  limit 0.
* ``f32_sum_error_of_bound``: the largest error of a float32 sum as a
  share of its Higham bound (``reference.F32_UNIT``).  The configuration
  states that bound (1.0); its limit lies between the largest reading of
  sound runs and the smallest of the control at the configuration's
  size, so it is the configuration's own.
* ``unanswered``: queries of the window that failed or never resolved:
  limit 0.

The limits are the configuration file's ``limits``.
"""
from __future__ import annotations

import json
from collections import Counter
from typing import Dict

import numpy as np

def _row_order(cols: dict) -> np.ndarray:
    return np.lexsort([cols[n] for n in reversed(list(cols))])


def same_multiset(a: dict, b: dict) -> bool:
    """Row-multiset equality of two column dicts (exact)."""
    if list(a) != list(b) or len({len(v) for v in [*a.values(),
                                                   *b.values()]}) > 1:
        return False
    if all(np.array_equal(a[n], b[n]) for n in a):
        return True                  # same rows in the same order
    oa, ob = _row_order(a), _row_order(b)
    return all(np.array_equal(a[n][oa], b[n][ob]) for n in a)


def _rows(cols: dict, idx) -> list:
    return [tuple(cols[n][i].item() for n in cols) for i in idx]


def _check_ties(got: dict, want, ties) -> bool:
    """Sort/limit: rows above the cut equal the reference's; the rest are
    distinct candidate rows at the cut, as many as the limit needs."""
    col, cut, cand, need = ties
    key = got[col]
    above, at = key > cut, key == cut
    if int(at.sum()) != need or int((key < cut).sum()):
        return False
    if not same_multiset({n: v[above] for n, v in got.items()}, want.cols):
        return False
    have = Counter(_rows(cand, range(len(cand[col]))))
    picked = Counter(_rows(got, np.nonzero(at)[0]))
    return all(have[r] >= k for r, k in picked.items())


def compare(got: Dict[str, np.ndarray], want) -> tuple:
    """(exact parts equal, largest f32 error as a share of its bound)."""
    if list(got) != list(want.cols):
        return False, 0.0
    if want.ties is not None:
        return _check_ties(got, want, want.ties), 0.0
    if len({len(v) for v in [*got.values(), *want.cols.values()]}) != 1:
        return False, 0.0
    if not want.tol:
        return same_multiset(got, want.cols), 0.0
    # one row per group: align on the group key (the first column)
    order = np.argsort(got[next(iter(got))], kind="stable")
    ok, worst = True, 0.0
    for name, w in want.cols.items():
        g = got[name][order]
        if name in want.tol:
            err = np.abs(g.astype(np.float64) - w)
            share = err / np.maximum(want.tol[name],
                                     np.finfo(np.float64).tiny)
            worst = max(worst, float(np.max(share, initial=0.0)))
        elif not np.array_equal(g.astype(np.int64), w.astype(np.int64)):
            ok = False
    return ok, worst


def check_answers(answers, reference, families) -> dict:
    """Compare every answered query with the reference.

    ``answers``: (family, params, columns or None) per query of the
    window; ``families``: name -> family module.  Returns the compared
    numbers by name.  The reference answers each distinct query once,
    for every answer of it, and holds one reference answer at a time."""
    mismatched, unanswered, worst = 0, 0, 0.0
    same_query: Dict[tuple, list] = {}
    for fam, params, got in answers:
        if got is None:
            unanswered += 1
            continue
        key = (fam, json.dumps(params, sort_keys=True))
        same_query.setdefault(key, []).append((params, got))
    for (fam, _), gots in same_query.items():
        want = families[fam].reference(reference, gots[0][0])
        for _, got in gots:
            ok, share = compare(got, want)
            mismatched += int(not ok)
            worst = max(worst, share)
    return {"mismatched_answers": mismatched,
            "f32_sum_error_of_bound": worst, "unanswered": unanswered}


def verdict(numbers: dict, limits: dict) -> bool:
    return all(numbers[k] <= limits[k] for k in limits)


def report(numbers: dict, limits: dict) -> dict:
    """Each compared number beside its limit, for the result line."""
    return {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
