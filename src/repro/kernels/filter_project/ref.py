"""Pure-jnp oracle for the fused filter/parse scan kernel."""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax.numpy as jnp

# Predicate program IR (static): postfix ops over a stack.
#   ("lt"|"le"|"gt"|"ge"|"eq"|"ne", col_idx, const)    -> push col OP const
#   ("ltc"|"lec"|"gtc"|"gec"|"eqc"|"nec", ia, ib)      -> push col_a OP col_b
#   ("in", col_idx, values)                            -> push membership
#   ("const", bool)                                    -> push constant mask
#   ("and",) / ("or",)                                 -> pop 2, push
#   ("not",)                                           -> pop 1, push
# A float const with a fractional part against an integer column folds
# into an exact integer compare at trace time (f32 promotion would be
# inexact beyond 2^24); col-col compares over mixed dtypes promote both
# sides to f32 (matching jnp's promotion in the XLA path — inexact
# beyond 2^24, like every f32 compare in the engine).
#
# SLOTTED programs (the plan-shape form): the const position of a
# compare may instead be ``("$i", j)`` / ``("$f", j)`` — a reference
# into the runtime ``iconsts`` / ``fconsts`` operand arrays.  A slotted
# program carries no literal values, so every literal variant of one
# predicate template shares a single static program (and a single
# trace).  Operand arrays are ``(k,)`` for one query or ``(n_q, k)``
# for a window batch, in which case the evaluated mask broadcasts to
# ``(n_q, block)`` — n queries in one pass over the same columns.
PredProgram = Tuple[tuple, ...]

_CMP = {
    "lt": lambda a, b: a < b, "le": lambda a, b: a <= b,
    "gt": lambda a, b: a > b, "ge": lambda a, b: a >= b,
    "eq": lambda a, b: a == b, "ne": lambda a, b: a != b,
}

# col-col variants -> base compare op
_CMP_CC = {k + "c": k for k in _CMP}

# kernel opcode <-> relational op symbol (for constant folding)
_CMP_OPSYM = {"lt": "<", "le": "<=", "gt": ">", "ge": ">=",
              "eq": "==", "ne": "!="}
_SYM_CMP = {v: k for k, v in _CMP_OPSYM.items()}


def _bcast(x: jnp.ndarray, bshape) -> jnp.ndarray:
    """Broadcast an operand to the batch shape (explicitly, so the
    Pallas TPU lowering never sees an implicit rank-mismatched op)."""
    if bshape is None or x.shape == tuple(bshape):
        return x
    if x.ndim == 1 and x.shape[0] == bshape[0] != bshape[1]:
        x = x[:, None]            # (n_q,) slot column -> (n_q, 1)
    return jnp.broadcast_to(x, bshape)


def eval_program(program: PredProgram, cols: Sequence[jnp.ndarray],
                 iconsts: Optional[jnp.ndarray] = None,
                 fconsts: Optional[jnp.ndarray] = None,
                 bshape: Optional[Tuple[int, int]] = None) -> jnp.ndarray:
    stack = []
    for op in program:
        if op[0] in _CMP:
            _, idx, const = op
            c = cols[idx]
            if isinstance(const, tuple):   # slot reference
                arr = iconsts if const[0] == "$i" else fconsts
                v = arr[..., const[1]]
                if v.ndim == 1:
                    v = v[:, None]         # (n_q,) -> (n_q, 1) row consts
                stack.append(_CMP[op[0]](_bcast(c, bshape),
                                         _bcast(v, bshape)))
                continue
            if (isinstance(const, float) and not float(const).is_integer()
                    and jnp.issubdtype(c.dtype, jnp.integer)):
                from ...relational.expr import fold_int_cmp

                folded = fold_int_cmp(_CMP_OPSYM[op[0]], float(const),
                                      bits=jnp.iinfo(c.dtype).bits)
                if folded[0] == "all":
                    fill = jnp.ones_like if folded[1] else jnp.zeros_like
                    stack.append(_bcast(fill(c, dtype=jnp.bool_), bshape))
                    continue
                _, opsym, b = folded
                stack.append(_CMP[_SYM_CMP[opsym]](
                    _bcast(c, bshape),
                    _bcast(jnp.asarray(b, c.dtype), bshape)))
                continue
            stack.append(_CMP[op[0]](_bcast(c, bshape),
                                     _bcast(jnp.asarray(const, c.dtype),
                                            bshape)))
        elif op[0] == "in":
            _, idx, values = op
            c = cols[idx]
            m = jnp.zeros(c.shape, jnp.bool_)
            is_int = jnp.issubdtype(c.dtype, jnp.integer)
            info = jnp.iinfo(c.dtype) if is_int else None
            for v in values:
                if is_int and isinstance(v, float):
                    if not float(v).is_integer():
                        continue            # an int never equals a fraction
                    v = int(v)
                if is_int and not (info.min <= int(v) <= info.max):
                    continue                # out of range: never equal
                m = m | (c == jnp.asarray(v, c.dtype))
            stack.append(_bcast(m, bshape))
        elif op[0] == "const":
            shape = tuple(bshape) if bshape is not None else cols[0].shape
            fill = jnp.ones if op[1] else jnp.zeros
            stack.append(fill(shape, jnp.bool_))
        elif op[0] in _CMP_CC:
            _, ia, ib = op
            a, b = cols[ia], cols[ib]
            if a.dtype != b.dtype:
                a, b = a.astype(jnp.float32), b.astype(jnp.float32)
            stack.append(_bcast(_CMP[_CMP_CC[op[0]]](a, b), bshape))
        elif op[0] == "and":
            b, a = stack.pop(), stack.pop()
            stack.append(a & b)
        elif op[0] == "or":
            b, a = stack.pop(), stack.pop()
            stack.append(a | b)
        elif op[0] == "not":
            stack.append(~stack.pop())
        else:
            raise ValueError(op)
    (mask,) = stack
    return mask


def filter_scan_ref(columns: Sequence[jnp.ndarray], program: PredProgram,
                    nrows: int | jnp.ndarray) -> jnp.ndarray:
    """Returns mask bool (N,)."""
    n = columns[0].shape[0]
    return eval_program(program, columns) & (jnp.arange(n) < nrows)


def filter_scan_batch_ref(columns: Sequence[jnp.ndarray],
                          program: PredProgram, nrows: int | jnp.ndarray,
                          iconsts: jnp.ndarray, fconsts: jnp.ndarray
                          ) -> jnp.ndarray:
    """Batched oracle: one pass over the columns evaluates a SLOTTED
    program for every row of the const arrays at once.

    Returns mask bool (n_q, N).
    """
    n = columns[0].shape[0]
    n_q = iconsts.shape[0]
    mask = eval_program(program, columns, iconsts=iconsts,
                        fconsts=fconsts, bshape=(n_q, n))
    return mask & (jnp.arange(n)[None, :] < nrows)


def parse_i32_ref(digits: jnp.ndarray) -> jnp.ndarray:
    """(n, 10) uint8 zero-padded decimal digits -> int32 (oracle)."""
    pows = jnp.asarray([10**k for k in range(9, -1, -1)], jnp.int32)
    return jnp.einsum("nd,d->n", digits.astype(jnp.int32) - 48, pows,
                      preferred_element_type=jnp.int32)
