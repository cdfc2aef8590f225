"""Fused columnar filter-scan Pallas kernel (the paper's hot path).

The paper's micro-benchmarks show scan+parse+filter dominates query
time for CSV inputs (§6.3).  On TPU we adapt the insight rather than
port row-wise CPU code:

  * columns stream HBM → VMEM in row-blocks (BlockSpec over the row
    dim, block size a multiple of the 8×128 VPU tile);
  * the predicate program is STATIC — the kernel body is specialized at
    trace time to the query's predicate, so the whole disjunction of a
    covering expression evaluates in registers in one pass (exactly the
    shared-operator fusion a CE needs);
  * optional fixed-width decimal parse runs as an int32 Horner sweep
    over the (block, 10) digit tile, fusing the CSV "parse+typecast"
    cost in;
  * the output is the boolean mask alone; the selected count and the
    compaction (data-dependent shape) stay outside in XLA, where a
    reduction or scatter is already optimal — a TPU kernel gains
    nothing there.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import PredProgram, eval_program

DEFAULT_BLOCK = 2048  # rows per block: 2048*4B = 8 KiB/column in VMEM


def _kernel_body(program: PredProgram, n_cols: int, n_q: int,
                 block: int, nrows_ref, ic_ref, fc_ref, *refs):
    col_refs = refs[:n_cols]
    mask_ref = refs[n_cols]
    bid = pl.program_id(0)

    cols = [r[...] for r in col_refs]
    # the program is static, so the whole postfix evaluation unrolls at
    # trace time into plain VPU element-wise ops (see ref.eval_program —
    # shared with the XLA oracle so both paths agree bit-for-bit).  One
    # pass over the block evaluates every query's slotted program row:
    # the (n_q, k) const arrays broadcast against the (block,) columns,
    # giving an (n_q, block) mask
    mask = eval_program(program, cols, iconsts=ic_ref[...],
                        fconsts=fc_ref[...], bshape=(n_q, block))

    row0 = bid * block
    # 2-D iota: TPU cannot lower a 1-D iota (see pallas guide)
    valid = (row0 + jax.lax.broadcasted_iota(jnp.int32, (n_q, block), 1)
             ) < nrows_ref[0]
    mask_ref[...] = mask & valid


def _scan_call(columns, program, nrows, iconsts, fconsts, block,
               interpret):
    """mask bool (n_q, N)."""
    n = columns[0].shape[0]
    assert n % block == 0, (n, block)
    grid = n // block
    n_cols = len(columns)
    n_q, ki = iconsts.shape
    kf = fconsts.shape[1]

    in_specs = [
        pl.BlockSpec((1,), lambda i: (0,)),            # nrows scalar
        pl.BlockSpec((n_q, ki), lambda i: (0, 0)),     # int consts
        pl.BlockSpec((n_q, kf), lambda i: (0, 0)),     # float consts
    ]
    in_specs += [pl.BlockSpec((block,), lambda i: (i,))
                 for _ in range(n_cols)]
    kernel = functools.partial(_kernel_body, program, n_cols, n_q, block)
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((n_q, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n_q, n), jnp.bool_),
        interpret=interpret,
    )(jnp.asarray(nrows, jnp.int32).reshape(1), iconsts, fconsts,
      *columns)


@functools.partial(jax.jit,
                   static_argnames=("program", "block", "interpret"))
def filter_scan(columns: Tuple[jnp.ndarray, ...], program: PredProgram,
                nrows, *, block: int = DEFAULT_BLOCK,
                interpret: bool = False):
    """Blocked fused predicate scan of one LITERAL program (a batch of
    one with empty const operands).

    Args:
      columns: tuple of (N,) int32/float32 column arrays, N % block == 0.
      program: static postfix predicate program (see ref.PredProgram).
      nrows: live row count (rows beyond it never match).
    Returns:
      mask bool (N,).
    """
    unused = jnp.zeros((1, 1), jnp.int32)
    return _scan_call(columns, program, nrows, unused,
                      unused.astype(jnp.float32), block, interpret)[0]


@functools.partial(jax.jit,
                   static_argnames=("program", "block", "interpret"))
def filter_scan_batch(columns: Tuple[jnp.ndarray, ...],
                      program: PredProgram, nrows,
                      iconsts: jnp.ndarray, fconsts: jnp.ndarray, *,
                      block: int = DEFAULT_BLOCK,
                      interpret: bool = False):
    """Window-batched fused predicate scan: n queries, ONE launch.

    The program is SLOTTED — literals live in the ``(n_q, k)`` operand
    arrays, not the static program — so every window of the same plan
    shape reuses one trace, and the columns stream HBM -> VMEM once for
    all n queries instead of once per query.

    Args:
      columns: tuple of (N,) numeric column arrays, N % block == 0.
      program: static slotted postfix program (see ref.PredProgram).
      nrows: live row count (rows beyond it never match).
      iconsts / fconsts: (n_q, k_i) int32 / (n_q, k_f) float32 operand
        arrays (k >= 1; pad with zeros when a class is unused).
    Returns:
      mask bool (n_q, N).
    """
    return _scan_call(columns, program, nrows, iconsts, fconsts, block,
                      interpret)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def parse_i32(digits: jnp.ndarray, *, block: int = DEFAULT_BLOCK,
              interpret: bool = False) -> jnp.ndarray:
    """Fixed-width decimal parse: (N, 10) uint8 -> int32 (N,).

    Horner's rule in int32: exact for every 10-digit field below 2^31,
    and wraps exactly like the int32 dot of ``ref.parse_i32_ref``
    beyond it.
    """
    n = digits.shape[0]
    assert n % block == 0 and digits.shape[1] == 10

    def body(digits_ref, out_ref):
        d = digits_ref[...].astype(jnp.int32) - 48
        acc = d[:, 0]
        for k in range(1, 10):
            acc = acc * 10 + d[:, k]
        out_ref[...] = acc

    return pl.pallas_call(
        body,
        grid=(n // block,),
        in_specs=[pl.BlockSpec((block, 10), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.int32),
        interpret=interpret,
    )(digits)
