"""Per-operator probe: compile seconds and device seconds of the main
path's heaviest operators at the TPC-DS SF10 fact capacity, each beside
the alternative it was chosen over.

  join probe      binary search (``_join_probe``), the same search with
                  ``method="sort"``, and the direct-address table
                  (``_join_probe_dense``) that the engine picks when the
                  build keys' range fits the probe side
  range read      build + dense probe with and without the host read
                  of the build keys' [min, max] between them (the read
                  that picks the probe, one round trip per join)
  compaction      ``_compact_nz`` (one sort of unique keys, the
                  columns carried through it or gathered after it)
                  against the tiled count + scatter it replaced, the
                  stable two-operand sort, and ``jnp.nonzero``, at the
                  fact capacity and at 2^22 rows, to half and to a
                  32nd of the rows; the sort alone (``_select_rows``)
                  and with the columns carried beside them
  join expansion  a join with unique build keys through
                  ``_select_rows`` + ``_join_gather_unique`` against
                  ``_join_expand`` + per-column gathers, the fact side
                  into a tenth-matching item dimension
  mask            the Pallas filter kernel against the XLA evaluation
                  of the same slotted program, 8 queries over 2 columns

Each op is compiled ahead of time (``lower().compile()``, the compile
seconds) and then run ``--repeats`` times; the best run is reported.
The alternatives must give the same result, or the probe exits 1.

Run:  python benchmarks/probe_ops.py                 (TPU, 2^25 rows)
      JAX_PLATFORMS=cpu python benchmarks/probe_ops.py --log2-rows 16
The last line is one JSON object with every measurement.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from functools import partial

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels.filter_project.ops import filter_mask_batch  # noqa: E402
from repro.relational.physical import (  # noqa: E402
    I32_SENTINEL, _compact_nz, _join_build, _join_expand,
    _join_gather_unique, _join_probe, _join_probe_dense, _select_rows)
from repro.relational.schema import next_pow2  # noqa: E402

BUILD_ROWS = 2000          # item dimension at SF10 (the F1/F6 join)
BUILD_CAP = 2048


@jax.jit
def probe_sort_method(lk, rk_sorted, l_nrows):
    """``_join_probe`` with searchsorted's sort-based method."""
    valid = jnp.arange(lk.shape[0]) < l_nrows
    keys = jnp.where(valid, lk, I32_SENTINEL)
    lo = jnp.searchsorted(rk_sorted, keys, side="left", method="sort")
    hi = jnp.searchsorted(rk_sorted, keys, side="right", method="sort")
    m = jnp.where(valid & (keys != I32_SENTINEL), hi - lo, 0)
    return lo, m, jnp.sum(m)


def _tiled_count(mask, tile=1024):
    """Inclusive running count as ``(rows, tile)`` tiles plus a scan of
    the tile totals."""
    x = mask.astype(jnp.int32)
    n = x.shape[0]
    if n <= tile or n % tile:
        return jnp.cumsum(x)
    tiles = jnp.cumsum(x.reshape(n // tile, tile), axis=1)
    before = jnp.cumsum(tiles[:, -1]) - tiles[:, -1]
    return (tiles + before[:, None]).reshape(n)


@partial(jax.jit, static_argnames=("new_cap",))
def compact_scatter(mask, new_cap, *cols):
    """The compaction through a tiled count and one scatter of each set
    row's index to its rank (the form the sort replaced)."""
    rank = jnp.where(mask, _tiled_count(mask) - 1, new_cap)
    rows = jnp.arange(mask.shape[0], dtype=jnp.int32)
    sel = jnp.zeros((new_cap,), jnp.int32).at[rank].set(rows, mode="drop")
    return tuple(jnp.take(c, sel, axis=0) for c in cols)


@partial(jax.jit, static_argnames=("new_cap",))
def compact_stable_sort(mask, new_cap, *cols):
    """The compaction through a stable two-operand sort of (~mask,
    row)."""
    order = jnp.argsort(~mask, stable=True)[:new_cap]
    sel = jnp.where(jnp.arange(new_cap) < jnp.sum(mask), order, 0)
    return tuple(jnp.take(c, sel, axis=0) for c in cols)


@partial(jax.jit, static_argnames=("new_cap",))
def compact_flat_nonzero(mask, new_cap, *cols):
    """The compaction through ``jnp.nonzero`` (two flat cumsums and a
    bincount)."""
    (sel,) = jnp.nonzero(mask, size=new_cap, fill_value=0)
    return tuple(jnp.take(c, sel, axis=0) for c in cols)


def measure(fn, args, kwargs=None, repeats=3):
    """(compile seconds, best run seconds, result) of a jitted ``fn``."""
    kwargs = kwargs or {}
    t0 = time.perf_counter()
    compiled = fn.lower(*args, **kwargs).compile()
    compile_s = time.perf_counter() - t0
    best, out = float("inf"), None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(*args))
        best = min(best, time.perf_counter() - t0)
    return compile_s, best, out


def timed_host(fn, repeats=3):
    """(first seconds, best seconds, result) of a host-level call."""
    times, out = [], None
    for _ in range(repeats + 1):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return times[0], min(times[1:]), out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--log2-rows", type=int, default=25)
    parser.add_argument("--repeats", type=int, default=3)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)
    cap = 1 << args.log2_rows
    rng = np.random.default_rng(args.seed)
    dev = jax.devices()[0]
    report = {"platform": dev.platform, "kind": dev.device_kind,
              "rows": cap}
    bad = []

    def emit(name, **fields):
        report[name] = fields
        print(name, json.dumps(fields), flush=True)

    # -- join probe: 2^25 fact keys into the 2,000-row item dimension
    lk = jnp.asarray(rng.integers(0, BUILD_ROWS, cap, dtype=np.int32))
    rk = jnp.asarray(np.concatenate([
        rng.permutation(BUILD_ROWS).astype(np.int32),
        np.zeros(BUILD_CAP - BUILD_ROWS, np.int32)]))
    l_nrows, r_nrows = jnp.int32(cap - 11), jnp.int32(BUILD_ROWS)
    _, rks, kmin, kmax, _ = jax.block_until_ready(_join_build(rk, r_nrows))
    span = next_pow2(int(kmax) - int(kmin) + 1)
    probes = {
        "probe_binary": (_join_probe, (lk, rks, l_nrows), {}),
        "probe_sort_method": (probe_sort_method, (lk, rks, l_nrows), {}),
        "probe_dense": (_join_probe_dense,
                        (lk, rks, l_nrows, kmin, kmax), {"span": span}),
    }
    ref = None
    for name, (fn, fargs, kw) in probes.items():
        c, r, (lo, m, total) = measure(fn, fargs, kw, args.repeats)
        emit(name, compile_s=c, best_s=r)
        lo, m = np.asarray(lo), np.asarray(m)
        if ref is None:
            ref = (lo, m, int(total))
        elif not (np.array_equal(m, ref[1]) and int(total) == ref[2]
                  and np.array_equal(lo[m > 0], ref[0][ref[1] > 0])):
            bad.append(name)

    # -- the host read of the build keys' range (one per join): build +
    # read + dense probe, against the same two dispatches with the span
    # already known (no read between them)
    def build_probe(read: bool):
        _, rks, lo_k, hi_k, _ = _join_build(rk, r_nrows)
        if read:
            jax.device_get((lo_k, hi_k))
        return _join_probe_dense(lk, rks, l_nrows, lo_k, hi_k, span=span)

    for name, read in (("join_with_range_read", True),
                       ("join_without_range_read", False)):
        first, best, _ = timed_host(lambda: build_probe(read),
                                    args.repeats)
        emit(name, first_s=first, best_s=best)

    # -- compaction to half the rows, 4 f32 columns (an F2 projection)
    for lg in sorted({args.log2_rows, min(args.log2_rows, 22)}):
        n = 1 << lg
        mask = jnp.asarray(rng.random(n) < 0.4)
        cols = [jnp.asarray(rng.random(n, dtype=np.float32))
                for _ in range(4)]
        for frac in (2, 32):
            outs = {}
            for name, fn in (("compact_sort", _compact_nz),
                             ("compact_scatter", compact_scatter),
                             ("compact_stable_sort", compact_stable_sort),
                             ("compact_flat_nonzero", compact_flat_nonzero)):
                fixed = jax.jit(lambda m, *c, fn=fn, k=n // frac:
                                fn(m, k, *c))
                c, r, out = measure(fixed, (mask, *cols), {}, args.repeats)
                emit(f"{name}_2^{lg}_to_1/{frac}", compile_s=c, best_s=r)
                outs[name] = [np.asarray(o) for o in out]
            ref = outs.pop("compact_sort")
            if not all(np.array_equal(a, b) for out in outs.values()
                       for a, b in zip(ref, out)):
                bad.append(f"compact_2^{lg}_to_1/{frac}")
        for carry in (False, True):
            c, r, _ = measure(_select_rows, (mask, *cols),
                              {"carry": carry}, args.repeats)
            emit(f"select_sort{'_carry4' if carry else ''}_2^{lg}",
                 compile_s=c, best_s=r)

    # -- join expansion, unique build keys: the fact side (2 key/value
    # columns gathered) into an item dimension of BUILD_ROWS keys out of
    # ten times as many, so a tenth of the probe rows match
    fk = jnp.asarray(rng.integers(0, 10 * BUILD_ROWS, cap, dtype=np.int32))
    fv = jnp.asarray(rng.random(cap, dtype=np.float32))
    ik = jnp.asarray(np.concatenate([
        rng.choice(10 * BUILD_ROWS, BUILD_ROWS, replace=False),
        np.zeros(BUILD_CAP - BUILD_ROWS)]).astype(np.int32))
    iv = jnp.asarray(rng.random(BUILD_CAP, dtype=np.float32))
    order, rks, lo_k, hi_k, dup = _join_build(ik, r_nrows)
    jspan = next_pow2(int(hi_k) - int(lo_k) + 1)
    lo, m, total = jax.block_until_ready(_join_probe_dense(
        fk, rks, l_nrows, lo_k, hi_k, span=jspan))
    total = int(total)
    out_cap = next_pow2(total)
    assert not bool(dup)

    def expand_repeat():
        li, ri = _join_expand(lo, m, out_cap)
        return (jnp.take(fk, li), jnp.take(fv, li),
                jnp.take(jnp.take(ik, order), ri),
                jnp.take(jnp.take(iv, order), ri))

    def expand_unique():
        keys, carried = _select_rows(m, lo, fk, fv, carry=True)
        return _join_gather_unique(keys, carried, order, out_cap, 3,
                                   lo, fk, fv, ik, iv)

    joined = {}
    for name, fn in (("expand_unique", expand_unique),
                     ("expand_repeat", expand_repeat)):
        first, best, out = timed_host(fn, args.repeats)
        emit(name, first_s=first, best_s=best, rows=total, out_cap=out_cap)
        joined[name] = [np.asarray(o)[:total] for o in out]
    if not all(np.array_equal(a, b) for a, b in zip(*joined.values())):
        bad.append("expand")

    # -- mask: 8 slotted queries over 2 columns
    program = (("gt", 0, ("$f", 0)), ("ge", 1, ("$i", 0)), ("and",))
    mcols = (jnp.asarray(rng.random(cap, dtype=np.float32) * 100),
             jnp.asarray(rng.integers(0, 100, cap, dtype=np.int32)))
    ic = jnp.asarray(np.arange(8, dtype=np.int32)[:, None] * 5)
    fc = jnp.asarray(np.arange(8, dtype=np.float32)[:, None] * 10)
    masks = {}
    for name, use_pallas in (("mask_pallas", True), ("mask_xla", False)):
        first, best, out = timed_host(lambda: filter_mask_batch(
            mcols, program, jnp.int32(cap - 5), ic, fc,
            block=min(2048, cap), use_pallas=use_pallas), args.repeats)
        emit(name, first_s=first, best_s=best)
        masks[name] = [np.asarray(o) for o in out]
    if not all(np.array_equal(a, b) for a, b in zip(*masks.values())):
        bad.append("mask")

    report["mismatches"] = bad
    print(json.dumps(report))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
