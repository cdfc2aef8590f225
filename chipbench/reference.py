"""The plain reference: vectorized NumPy over the host columns.

It imports nothing of the program.  Filters are boolean masks, joins are
lookups on the dense dimension keys, group-bys are ``np.bincount`` over
small dense keys.  Each query family (``chipbench/families/<F>.py``)
writes its answer from its parameters with these helpers.

``Reference(catalog, lower=True)`` is the control: every float32 column
rounded to bfloat16 before anything reads it, the step a later change
that stores columns in a narrower type would take.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional

import numpy as np

# f32 sums: the engine adds f32 values in an order the device chooses,
# the reference adds the same values in f64.  Any f32 summation order
# stays within gamma_(n-1) * sum(|x|) of the exact sum, gamma_k =
# k*u / (1 - k*u) with u = 2**-24 (Higham, "Accuracy and Stability of
# Numerical Algorithms", 2nd ed., Eq. 4.4): that bound is the limit per
# group of n rows.
F32_UNIT = 2.0 ** -24


@dataclass
class Answer:
    """A query's expected result: columns in the engine's output order,
    the f32 bound of each summed column, and for a sort/limit the rows
    that tie at the cut."""

    cols: Dict[str, np.ndarray]
    tol: Dict[str, np.ndarray] = field(default_factory=dict)
    # sort/limit only: (sort column, the last kept value, every candidate
    # row whose sort key equals it, how many of them the limit keeps)
    ties: Optional[tuple] = None

    def result(self) -> Dict[str, np.ndarray]:
        """The answer as the engine would return it: for a sort/limit the
        rows above the cut, then the first candidates at the cut."""
        if self.ties is None:
            return self.cols
        _, _, cand, need = self.ties
        return {n: np.concatenate([v, cand[n][:need]])
                for n, v in self.cols.items()}


def bf16_round(x: np.ndarray) -> np.ndarray:
    """float32 -> nearest bfloat16 (ties to even), kept as float32."""
    b = x.astype(np.float32).view(np.uint32).astype(np.uint64)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    return b.astype(np.uint32).view(np.float32)


class Reference:
    """Host columns plus per-sale dimension attributes, gathered once
    and shared by every query of a check."""

    def __init__(self, catalog: dict, lower: bool = False):
        self.lower = lower
        if lower:
            catalog = {t: {n: (bf16_round(a) if a.dtype == np.float32
                               else a) for n, a in cols.items()}
                       for t, cols in catalog.items()}
        self.t = catalog
        for table, key in (("item", "i_item_sk"),
                           ("customer", "c_customer_sk"),
                           ("store", "s_store_sk"),
                           ("date_dim", "d_date_sk")):
            k = catalog[table][key]
            if not np.array_equal(k, np.arange(len(k))):
                raise ValueError(f"{key} is not dense: lookup joins need it")
        self._per_sale: Dict[tuple, np.ndarray] = {}

    def ss(self, name: str) -> np.ndarray:
        return self.t["store_sales"][name]

    def per_sale(self, table: str, col: str, fk: str) -> np.ndarray:
        """``table.col`` of each sale's dimension row (gathered once)."""
        key = (table, col)
        if key not in self._per_sale:
            self._per_sale[key] = self.t[table][col][self.ss(fk)]
        return self._per_sale[key]


def str_eq(col: np.ndarray, value: str) -> np.ndarray:
    b = value.encode()[: col.shape[1]]
    pad = np.zeros(col.shape[1], np.uint8)
    pad[: len(b)] = np.frombuffer(b, np.uint8)
    return (col == pad).all(axis=1)


def group_by(keys: np.ndarray, key_name: str, sums: dict,
             counts: Optional[str] = None) -> Answer:
    """Group-by over small non-negative integer keys: one output row per
    key present, ascending; f64 sums of f32 columns with their bounds,
    exact sums of integer columns, and optionally the row count."""
    k = keys.astype(np.int64)
    n = np.bincount(k)
    present = np.nonzero(n)[0]
    n = n[present]
    cols = {key_name: present.astype(np.int32)}
    tol = {}
    for name, vals in sums.items():
        v64 = vals.astype(np.float64)
        s = np.bincount(k, weights=v64)[present]
        if vals.dtype.kind == "f":
            gamma = (n - 1) * F32_UNIT / (1 - (n - 1) * F32_UNIT)
            tol[name] = gamma * np.bincount(k, weights=np.abs(v64))[present]
            cols[name] = s
        else:
            cols[name] = s.astype(np.int64)
    if counts is not None:
        cols[counts] = n.astype(np.int64)
    return Answer(cols, tol)
