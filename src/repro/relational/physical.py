"""Physical execution of logical plans.

The Spark analog: every operator materializes a fixed-shape distributed
columnar relation (padded to a power-of-two capacity so jit caches hit
across queries).  Orchestration is host-side Python — exactly like a
Spark driver launching stages — while each operator body is a jitted
JAX function that runs SPMD when the arrays carry a NamedSharding.

Two execution paths (see ROADMAP.md "Execution paths"):

  * **eager** — one jitted call per operator, host-synchronized row
    counts after every data-dependent-shape operator (seed behavior;
    ``ExecContext(fuse=False, defer_sync=False, scan_cache=None)``);
  * **fused** (default) — ``relational.fuse`` collapses leaf→Filter*→
    Project chains into single-dispatch :class:`FusedPipeline` nodes, a
    device scan cache memoizes padded device columns across queries,
    and cardinality-estimate-driven output capacities defer the host
    sync (``int(count)``) until after the pipeline has dispatched,
    recompacting only on estimate overflow.

Storage formats (the paper's CSV vs Parquet axis):
  * ``csv``      — the table lives on "disk" (host memory) as one
    fixed-width UTF-8 byte matrix; a scan must move the WHOLE row bytes
    to the device and parse the needed fields with vectorized digit
    arithmetic (reproducing CSV parse/typecast cost).
  * ``columnar`` — typed host arrays per column; a scan moves only the
    needed columns (Parquet-analog column pruning).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.cache import CacheManager
from ..core.costmodel import CalibrationSample
from ..core.faults import DegradationEvent
from ..core.memory import MemoryPool
from ..core.telemetry import NOOP_SPAN
from . import expr as E
from . import logical as L
from .canonical import subsumes as _subsumes
from .fuse import FusedPipeline, fuse_plan
from .partition import (PartitionInfo, PartitionedCePlan,
                        pid_presence_from_mask, prune_parts,
                        restrict_to_parts)
from .schema import Schema, Table, empty_like, next_pow2

I32_SENTINEL = np.int32(2**31 - 1)


class CEMaterializationError(RuntimeError):
    """A shared covering relation failed to materialize.  Raised to
    every consumer of the poisoned ψ (the first failure marks it in
    ``ctx.failed_ces``) so the service can rerun each consumer on its
    unshared residual plan instead of letting one bad CE take down the
    whole window."""

    def __init__(self, psi: bytes, cause: Optional[BaseException] = None):
        self.psi = psi
        self.cause = cause
        why = f": {cause!r}" if cause is not None else ""
        super().__init__(
            f"covering relation ψ={psi.hex()[:12]} failed to "
            f"materialize{why}")

# deferred-sync capacity estimates get this much slack before the
# overflow-recompact path triggers (estimation error is one-sided cheap:
# undershoot costs a recompact, overshoot only pads the output)
EST_HEADROOM = 1.25


# ---------------------------------------------------------------------------
# host-side storage ("disk")
# ---------------------------------------------------------------------------
@dataclass
class TableStorage:
    name: str
    schema: Schema
    nrows: int
    fmt: str                      # "csv" | "columnar"
    columnar: Optional[Dict[str, np.ndarray]] = None
    csv_bytes: Optional[np.ndarray] = None        # (nrows, row_csv_bytes) u8
    # horizontal partition layout (relational.partition): when set, rows
    # are re-clustered so each partition is a contiguous range, scans go
    # through per-partition device cache entries, and filter predicates
    # prune partitions before scanning
    partitions: Optional[PartitionInfo] = None

    @property
    def disk_bytes(self) -> int:
        if self.fmt == "csv":
            return int(self.csv_bytes.size)
        return int(sum(a.nbytes for a in self.columnar.values()))


@dataclass
class ExecMetrics:
    bytes_read_disk: int = 0
    bytes_parsed: int = 0
    bytes_cached_read: int = 0
    bytes_scan_cache_read: int = 0
    rows_processed: int = 0
    # plan-shape compile cache: hits reuse a jitted fused pipeline keyed
    # by canonical plan shape (literals slotted out); misses traced one
    trace_hits: int = 0
    trace_misses: int = 0
    # window batching: shared dispatches and the queries they covered
    batched_dispatches: int = 0
    batched_queries: int = 0
    # slotted mask dispatches by route: the Pallas kernel, or the XLA
    # evaluation of the same program (kernel declined or route off)
    pallas_dispatches: int = 0
    xla_slotted_dispatches: int = 0
    # pid bitset pool (PR 8): resident bitsets used by lookups, the
    # partitions they pruned beyond statistics, and new recordings
    pid_hits: int = 0
    pid_pruned_parts: int = 0
    pid_records: int = 0
    # device->host reads: each waits for the device to finish what it
    # reads (see _to_host)
    host_syncs: int = 0
    # deferred-sync dispatches by operator, and those thrown away and
    # run again at the exact size (see _deferred_dispatch)
    deferred_dispatches: Dict[str, int] = field(default_factory=dict)
    redispatches: Dict[str, int] = field(default_factory=dict)
    # joins by path (see _exec_join): "unique" build keys gather the
    # matched probe rows, "expand" repeats a probe row per match
    joins: Dict[str, int] = field(default_factory=dict)

    def note_join(self, path: str) -> None:
        self.joins[path] = self.joins.get(path, 0) + 1

    def note_dispatch(self, op: str, redispatched: bool) -> None:
        d = self.deferred_dispatches
        d[op] = d.get(op, 0) + 1
        if redispatched:
            self.redispatches[op] = self.redispatches.get(op, 0) + 1


@dataclass
class ExecContext:
    catalog: Dict[str, TableStorage]
    cache: Optional[CacheManager] = None
    cache_plans: Dict[bytes, L.Node] = field(default_factory=dict)
    # psi -> cost-model savings estimate (Eq. 3 value), forwarded to the
    # memory manager at materialization time so benefit-per-byte
    # eviction can rank CE entries
    cache_values: Dict[bytes, float] = field(default_factory=dict)
    metrics: ExecMetrics = field(default_factory=ExecMetrics)
    # Optional sharding applied to row-dim of loaded columns.
    sharding: Optional[jax.sharding.Sharding] = None
    # emulate slow disk: per-byte sleep (used by benchmarks to model I/O)
    disk_latency_per_byte: float = 0.0
    # route numeric predicates through the Pallas filter-scan kernel
    # (TPU target; interpret mode on CPU — used by tests)
    use_pallas_filter: bool = False
    # collapse Scan→Filter*→Project chains into single-dispatch
    # FusedPipeline nodes (see relational.fuse)
    fuse: bool = True
    # device scan cache: (table, column, capacity, sharding) -> padded
    # device array, shared across queries/batches.  Either a budgeted
    # MemoryPool (Session default — evictable under the session-wide
    # device budget) or a raw dict (unbounded; kept for tests and
    # standalone ExecContexts).
    scan_cache: Optional[object] = None
    # cardinality estimator (duck-typed RelationalCostModel) enabling
    # deferred host synchronization: output capacities are picked from
    # estimates so operator pipelines dispatch without a blocking
    # int(count) per operator; the count validates afterwards and a
    # recompact runs only on estimate overflow
    cost_model: Optional[object] = None
    defer_sync: bool = True
    # partition pruning: fused pipelines over partitioned tables skip
    # partitions whose statistics refute the predicate (conservative —
    # disable to force the unpruned path, e.g. for bit-identity tests)
    prune: bool = True
    # plan-shape compile cache: route fused filters through SLOTTED
    # predicate programs (literals hoisted into operand arrays) so
    # recurring templates with fresh constants never re-trace; disable
    # to force the legacy literal-keyed jit path
    shape_cache: bool = True
    # strict cache key -> PartitionedCePlan for every partition-grained
    # CE this window selected: reads compose resident partitions from
    # the cache with per-partition recomputation of the cold ones
    partitioned_ces: Dict[bytes, PartitionedCePlan] = \
        field(default_factory=dict)
    # window-scoped memo of recomputed NON-admitted partitions: like a
    # whole-CE materialization, a cold partition is computed once per
    # window and shared by every consumer — but unlike admitted
    # entries it dies with the window's context instead of occupying
    # the budgeted cache.  Pinning is bounded by ONE device budget
    # (see _memo_put) — the same order as any operator's transient
    # output; beyond that the memo degrades to recompute-per-read
    # instead of holding unbounded device bytes the MCKP rejected.
    ce_part_memo: Dict[tuple, "Table"] = field(default_factory=dict)
    ce_part_memo_bytes: int = 0
    # optional core.faults.FaultInjector — the scan_h2d / kernel_launch /
    # ce_admission points fire through ctx.check_fault(...)
    faults: Optional[object] = None
    # strict keys of CEs whose materialization failed this window:
    # consumers of a poisoned CE fail fast (CEMaterializationError) so
    # the service can rerun them on their unshared residual plans
    failed_ces: set = field(default_factory=set)
    # core.memory.PidPool (or None): partition-ID bitsets recorded as a
    # side effect of fused execution and intersected on later lookups
    # to prune by observed history on top of the stats pruner
    pid_cache: Optional[object] = None
    # (table, canonical pred) -> partitions the pid intersection pruned
    # BEYOND statistics this window (read by service explain())
    pid_prune_log: Dict[tuple, int] = field(default_factory=dict)
    # DegradationEvents raised below the service layer (a failed pid
    # bitset read degrades to stats-only pruning here instead of
    # surfacing — a pid hit is an optimization, never a failure domain)
    degradations: list = field(default_factory=list)
    # optional relational.observe.Telemetry (PR 9): calibration samples
    # on CE materializations / cached reads, spans on H2D + dispatch
    # when tracing is enabled.  None for standalone contexts.
    telemetry: Optional[object] = None

    def check_fault(self, point: str, key=None) -> None:
        if self.faults is not None:
            self.faults.check(point, key=key)

    def span(self, name: str, **attrs):
        """A lifecycle span when tracing is on; the shared no-op
        context manager otherwise (zero allocations)."""
        tel = self.telemetry
        if tel is not None and tel.tracer.enabled:
            return tel.tracer.span(name, **attrs)
        return NOOP_SPAN

    def _memo_put(self, key: tuple, table: "Table") -> bool:
        allowance = float("inf")
        manager = getattr(self.cache, "manager", None) \
            if self.cache is not None else None
        if manager is not None:
            allowance = manager.device_budget
        if self.ce_part_memo_bytes + table.nbytes <= allowance:
            self.ce_part_memo[key] = table
            self.ce_part_memo_bytes += table.nbytes
            return True
        return False

    def _memo_drop(self, key: tuple) -> None:
        t = self.ce_part_memo.pop(key, None)
        if t is not None:
            self.ce_part_memo_bytes -= t.nbytes

    def estimate(self, kind: str, *args) -> Optional[int]:
        """Cardinality estimate for deferred sync; None -> eager sync."""
        if not self.defer_sync or self.cost_model is None:
            return None
        fn = getattr(self.cost_model, f"{kind}_estimate", None)
        if fn is None:
            return None
        return int(fn(*args))

    @classmethod
    def from_exec_config(cls, catalog: Dict[str, "TableStorage"], cfg,
                         *, cache: Optional[CacheManager] = None,
                         cost_model: Optional[object] = None,
                         scan_cache: Optional[object] = None,
                         pid_cache: Optional[object] = None
                         ) -> "ExecContext":
        """Build a context from anything shaped like an
        ``relational.service.ExecutionConfig`` (a Session mirrors the
        same attributes) — the single place execution-path knobs are
        translated into a context."""
        return cls(
            catalog=catalog, cache=cache,
            sharding=getattr(cfg, "sharding", None),
            disk_latency_per_byte=getattr(cfg, "disk_latency_per_byte",
                                          0.0),
            use_pallas_filter=getattr(cfg, "use_pallas_filter", False),
            fuse=cfg.fuse,
            defer_sync=cfg.defer_sync,
            prune=getattr(cfg, "prune", True),
            shape_cache=getattr(cfg, "shape_cache", True),
            cost_model=cost_model,
            scan_cache=scan_cache,
            pid_cache=pid_cache,
            faults=getattr(cfg, "fault_injector", None),
            telemetry=getattr(cfg, "_telemetry", None))


# ---------------------------------------------------------------------------
# jitted primitives (cached per static signature)
# ---------------------------------------------------------------------------
_POW10_I = jnp.asarray([10**k for k in range(9, -1, -1)], jnp.int32)
_POW10_F = jnp.asarray([10.0**k for k in range(7, -1, -1)], jnp.float32)


@jax.jit
def _parse_i32(digits: jnp.ndarray) -> jnp.ndarray:
    """(n, 10) uint8 zero-padded decimal digits -> int32."""
    d = digits.astype(jnp.int32) - 48
    return jnp.einsum("nd,d->n", d, _POW10_I,
                      preferred_element_type=jnp.int32)


@jax.jit
def _parse_f32(digits: jnp.ndarray) -> jnp.ndarray:
    """(n, 8) uint8 fractional digits -> float32 in [0, 1)."""
    d = digits.astype(jnp.float32)
    return jnp.einsum("nd,d->n", d - 48.0, _POW10_F) * jnp.float32(1e-8)


def _pred_mask_fn(pred_key, pred: E.Expr, names: Tuple[str, ...]):
    def f(nrows, *cols):
        columns = dict(zip(names, cols))
        mask = E.eval_expr(pred, columns)
        n = cols[0].shape[0]
        mask = mask & (jnp.arange(n) < nrows)
        return mask, jnp.sum(mask.astype(jnp.int32))
    return jax.jit(f)


_FN_CACHE: Dict[tuple, Callable] = {}


def _cached(key, builder):
    fn = _FN_CACHE.get(key)
    if fn is None:
        fn = _FN_CACHE[key] = builder()
    return fn


def _shape_cached(ctx: "ExecContext", key, builder):
    """``_cached`` variant for plan-SHAPE keys (literals slotted out),
    with hit/miss accounting: a miss here is a fresh trace of a fused
    pipeline; a hit means a recurring template reused the jitted fn."""
    fn = _FN_CACHE.get(key)
    if fn is None:
        ctx.metrics.trace_misses += 1
        fn = _FN_CACHE[key] = builder()
    else:
        ctx.metrics.trace_hits += 1
    return fn


@partial(jax.jit, static_argnames=("new_cap",))
def _compact(mask: jnp.ndarray, new_cap: int, *cols):
    """Bring mask-selected rows to the front; slice to new_cap."""
    order = jnp.argsort(~mask, stable=True)
    sel = order[:new_cap]
    return tuple(jnp.take(c, sel, axis=0) for c in cols)


def _selection_keys(mask: jnp.ndarray) -> jnp.ndarray:
    """One int32 key per row of a 1-D mask: a set row keys as itself,
    any other row as itself plus ``n``.  Sorted, the set rows come
    first, in ascending order; every key is unique, so the sort need
    not be stable (at 2^25 rows a stable sort takes the v5e compiler
    about five times as long)."""
    n = mask.shape[0]
    assert n <= 1 << 30, "selection keys must fit int32"
    rows = jnp.arange(n, dtype=jnp.int32)
    return jnp.where(mask, rows, rows + n)


def _carries(n: int, cap: int) -> bool:
    """Whether a compaction of ``n`` rows to ``cap`` sorts its columns
    along with the selection keys, or sorts the keys alone and gathers
    each output row through them.  On one v5e chip a gathered element
    cost 25-33 ns and a column carried through the sort about 1 ns a
    row (``benchmarks/probe_ops.py`` measures both), so the sort carries
    from ``cap >= n/16``; below that the gather is cheaper."""
    return 16 * cap >= n


def _carriable(c: jnp.ndarray) -> bool:
    """A column the sort can carry: one 4-byte value per row."""
    return c.ndim == 1 and c.dtype.itemsize == 4


def _sort_selection(mask: jnp.ndarray, cols, carry: bool):
    """``(keys, carried)``: the sorted selection keys of ``mask`` and,
    with ``carry``, every carriable column sorted along with them."""
    if not carry:
        return jax.lax.sort(_selection_keys(mask), is_stable=False), ()
    out = jax.lax.sort((_selection_keys(mask),)
                       + tuple(c for c in cols if _carriable(c)),
                       num_keys=1, is_stable=False)
    return out[0], tuple(out[1:])


def _head_selected(keys: jnp.ndarray, carried, cols, size: int):
    """The first ``size`` selected rows of every column, in ascending
    row order, row 0's value after them: a carried column is sliced
    from its sorted copy, any other gathered through the keys."""
    n = keys.shape[0]
    if size > n:
        keys = jnp.concatenate([keys, jnp.full((size - n,), n, jnp.int32)])
        carried = tuple(jnp.concatenate([s, jnp.zeros((size - n,), s.dtype)])
                        for s in carried)
    head = keys[:size]
    live = head < n
    sel = jnp.where(live, head, 0)
    outs, sorted_cols = [], iter(carried)
    for c in cols:
        if carried and _carriable(c):
            outs.append(jnp.where(live, next(sorted_cols)[:size], c[0]))
        else:
            outs.append(jnp.take(c, sel, axis=0))
    return tuple(outs)


@partial(jax.jit, static_argnames=("carry",))
def _select_rows(flags: jnp.ndarray, *cols, carry: bool = False):
    """``_sort_selection`` of the rows where ``flags`` (a mask, or
    per-row match counts) is nonzero, as its own program: the n-sized
    part of a compaction, dispatched once before the output is sized."""
    return _sort_selection(flags != 0, cols, carry)


@partial(jax.jit, static_argnames=("new_cap",))
def _take_selected(keys: jnp.ndarray, carried, new_cap: int, *cols):
    """The cap-sized part of a compaction (``_head_selected``)."""
    return _head_selected(keys, carried, cols, new_cap)


class _Selection:
    """A compaction of ``cols`` to the set rows of ``mask`` that may be
    sized more than once (see ``_deferred_dispatch``): the sort runs at
    most once per route (``_carries`` of each size asked for), and each
    size only slices and gathers.  ``keys``/``carried`` may come from a
    program that already sorted."""

    def __init__(self, mask, cols, keys=None, carried=()):
        self.mask, self.cols = mask, tuple(cols)
        self.keys, self.carried = keys, tuple(carried)

    def sort_for(self, new_cap: int):
        """``(keys, carried)`` for a compaction to ``new_cap``."""
        carry = (_carries(self.mask.shape[0], new_cap)
                 and any(_carriable(c) for c in self.cols))
        if carry and not self.carried:
            self.keys, self.carried = _select_rows(self.mask, *self.cols,
                                                   carry=True)
        elif self.keys is None:
            self.keys, _ = _select_rows(self.mask)
        return self.keys, self.carried

    def __call__(self, new_cap: int):
        return _take_selected(*self.sort_for(new_cap), new_cap, *self.cols)


def _compact_nz_impl(mask: jnp.ndarray, new_cap: int, *cols):
    """Compaction through one sort of unique keys (vs the stable
    two-operand argsort in ``_compact``), carrying the columns through
    the sort or gathering them after it as ``_carries`` says.

    Rows come out in ascending order — the same live rows, in the same
    order, as the stable argsort of ~mask; fill rows (beyond the
    selected count) repeat row 0, which is compaction slack every
    operator already tolerates.  Used where the output is sized once; a
    deferred-sync dispatch goes through ``_Selection``.  The plain
    ``_compact`` is kept as the seed eager behavior.
    """
    keys, carried = _sort_selection(mask, cols,
                                    _carries(mask.shape[0], new_cap))
    return _head_selected(keys, carried, cols, new_cap)


_compact_nz = partial(jax.jit, static_argnames=("new_cap",))(
    _compact_nz_impl)


def _sort_sentinel(k: jnp.ndarray):
    """Dtype-matched +inf analog for masking padding rows before a sort
    (int32 AND int64 keys get their exact integer max, not a float)."""
    if jnp.issubdtype(k.dtype, jnp.integer):
        return jnp.asarray(jnp.iinfo(k.dtype).max, k.dtype)
    return jnp.asarray(jnp.inf, k.dtype)


@partial(jax.jit, static_argnames=("asc_sentinel",))
def _sort_order(key: jnp.ndarray, nrows, asc_sentinel: bool):
    valid = jnp.arange(key.shape[0]) < nrows
    k = jnp.where(valid, key, _sort_sentinel(key))
    return jnp.argsort(k, stable=True)


@jax.jit
def _join_build(rk: jnp.ndarray, r_nrows):
    """Sorted build side, the [min, max] of its matchable keys (the
    sentinel never matches, so it is left out of the range), and
    whether two live build rows share a key."""
    masked = jnp.where(jnp.arange(rk.shape[0]) < r_nrows, rk, I32_SENTINEL)
    order = jnp.argsort(masked, stable=True)
    live = masked != I32_SENTINEL
    kmin = jnp.min(jnp.where(live, masked, I32_SENTINEL))
    kmax = jnp.max(jnp.where(live, masked, np.iinfo(np.int32).min))
    rk_sorted = jnp.take(masked, order)
    dup = jnp.any((rk_sorted[1:] == rk_sorted[:-1])
                  & (rk_sorted[1:] != I32_SENTINEL))
    return order, rk_sorted, kmin, kmax, dup


@jax.jit
def _join_probe(lk: jnp.ndarray, rk_sorted: jnp.ndarray, l_nrows):
    valid = jnp.arange(lk.shape[0]) < l_nrows
    keys = jnp.where(valid, lk, I32_SENTINEL)
    lo = jnp.searchsorted(rk_sorted, keys, side="left")
    hi = jnp.searchsorted(rk_sorted, keys, side="right")
    m = jnp.where(valid & (keys != I32_SENTINEL), hi - lo, 0)
    return lo, m, jnp.sum(m)


@partial(jax.jit, static_argnames=("span",))
def _join_probe_dense(lk: jnp.ndarray, rk_sorted: jnp.ndarray, l_nrows,
                      kmin, kmax, span: int):
    """``_join_probe`` through a direct-address table over the build
    keys' range: ``first[j]`` counts build keys below ``kmin + j``, so a
    probe key's (lo, m) is two gathers from the table instead of a
    binary search — log2(build) gathers over every probe row, which is
    what a TPU pays most for.  ``span`` (static) is a power of two
    covering ``kmax - kmin + 1``; (lo, m) equal ``_join_probe``'s
    wherever m > 0."""
    grid = kmin + jnp.arange(span + 1, dtype=jnp.int32)
    first = jnp.searchsorted(rk_sorted, grid, side="left")
    valid = jnp.arange(lk.shape[0]) < l_nrows
    inside = valid & (lk >= kmin) & (lk <= kmax)
    off = jnp.where(inside, lk - kmin, 0)
    lo = jnp.take(first, off)
    m = jnp.where(inside, jnp.take(first, off + 1) - lo, 0)
    return lo, m, jnp.sum(m)


@partial(jax.jit, static_argnames=("out_cap",))
def _join_expand(lo, m, out_cap):
    starts = jnp.cumsum(m) - m            # exclusive prefix
    li = jnp.repeat(jnp.arange(m.shape[0]), m,
                    total_repeat_length=out_cap)
    inner = jnp.arange(out_cap) - jnp.take(starts, li)
    ri = jnp.take(lo, li) + inner
    return li, ri


@partial(jax.jit, static_argnames=("out_cap", "n_sel"))
def _join_gather_unique(keys, carried, order, out_cap: int, n_sel: int,
                        *cols):
    """A join's output columns when no two build rows share a key: each
    probe row matches at most one build row, so the output rows are the
    matched probe rows in order (``keys``/``carried`` from
    ``_select_rows`` of the match counts), each with the build row at
    its ``lo``.  ``cols`` are ``lo`` and the probe columns (``n_sel`` in
    all, in the selection), then the build columns."""
    lo, *left = _head_selected(keys, carried, cols[:n_sel], out_cap)
    ri = jnp.take(order, lo)
    return (tuple(left)
            + tuple(jnp.take(c, ri, axis=0) for c in cols[n_sel:]))


@jax.jit
def _agg_seg_ids(nrows, *keys):
    n = keys[0].shape[0]
    valid = jnp.arange(n) < nrows
    sk = [jnp.where(valid, k, _sort_sentinel(k)) for k in keys]
    order = jnp.lexsort(tuple(reversed(sk)))
    sorted_valid = jnp.take(valid, order)
    sorted_keys = [jnp.take(k, order) for k in sk]
    newgrp = jnp.zeros((n,), jnp.bool_).at[0].set(True)
    for k in sorted_keys:
        newgrp = newgrp | (k != jnp.roll(k, 1))
    newgrp = newgrp & sorted_valid
    gid = jnp.cumsum(newgrp.astype(jnp.int32)) - 1
    n_groups = jnp.sum(newgrp)
    return order, gid, sorted_valid, n_groups


# ---------------------------------------------------------------------------
# operator implementations
# ---------------------------------------------------------------------------
def _device_put(arr: np.ndarray, ctx: ExecContext) -> jnp.ndarray:
    ctx.check_fault("scan_h2d")
    with ctx.span("scan.h2d", nbytes=int(arr.nbytes)):
        if ctx.disk_latency_per_byte:
            time.sleep(arr.nbytes * ctx.disk_latency_per_byte)
        if ctx.sharding is not None and arr.ndim >= 1:
            # a sharding that cannot be applied raises: placing the
            # array on one device instead would hide the layout change
            return jax.device_put(arr, ctx.sharding)
        return jnp.asarray(arr)


def _pad_rows(arr: np.ndarray, cap: int) -> np.ndarray:
    """Zero-pad the row dim to ``cap`` (no copy when already there)."""
    if cap == arr.shape[0]:
        return arr
    pad_shape = (cap - arr.shape[0],) + arr.shape[1:]
    return np.concatenate([arr, np.zeros(pad_shape, arr.dtype)], 0)


def _scan_pool_put(ctx: ExecContext, key: tuple, dev: jnp.ndarray,
                   benefit: float) -> None:
    """Single admission point for the scan pool (whole-table,
    per-partition, and assembled entries all rank under one benefit
    unit system); raw-dict caches (tests) just store."""
    sc = ctx.scan_cache
    if isinstance(sc, MemoryPool):
        nbytes = int(dev.size) * dev.dtype.itemsize
        sc.put(key, dev, nbytes=nbytes, benefit=benefit)
    elif sc is not None:
        sc[key] = dev


def _reread_benefit(ctx: ExecContext, host_nbytes: int) -> float:
    """Benefit of a scan entry: the re-read cost it saves per hit, in
    the SAME units as the CostModel's Eq. 3 values that CE entries
    carry (per-byte columnar io + modeled disk latency), so
    benefit-per-byte eviction ranks the two pools consistently."""
    io = getattr(getattr(ctx.cost_model, "c", None), "io_col", 1e-9)
    return host_nbytes * (io + ctx.disk_latency_per_byte)


def _scan_cached(ctx: ExecContext, key: tuple, host, cap: int,
                 host_nbytes: Optional[int] = None) -> jnp.ndarray:
    """Padded device column, memoized per (table, col, cap, sharding).

    Repeated scans across a batch (and across batches of the same
    Session) skip both the host-side pad copy and the host→device
    transfer — the dominant per-scan cost once plans are compiled.
    ``host`` may be a zero-arg callable building the host array lazily
    (with ``host_nbytes`` supplied for metrics): an expensive host-side
    assembly then only runs on a cache miss.
    """
    sc = ctx.scan_cache
    lazy = callable(host)
    nbytes = host_nbytes if lazy else host.nbytes
    if sc is not None:
        key = key + (cap, str(ctx.sharding))
        hit = sc.get(key)
        if hit is not None:
            ctx.metrics.bytes_scan_cache_read += nbytes
            return hit
    host_arr = host() if lazy else host
    dev = _device_put(_pad_rows(host_arr, cap), ctx)
    ctx.metrics.bytes_read_disk += host_arr.nbytes
    _scan_pool_put(ctx, key, dev, _reread_benefit(ctx, host_arr.nbytes))
    return dev


def _scan_part_cached(ctx: ExecContext, key: tuple,
                      host_slice: np.ndarray) -> jnp.ndarray:
    """UNPADDED device copy of one partition's rows, memoized per
    (table, column/"__csv__", "part", pid).  Partition-grained entries
    are what different prune sets share: a scan pruned to {1, 3} and a
    later one pruned to {3, 5} both reuse partition 3's bytes."""
    sc = ctx.scan_cache
    if sc is not None:
        hit = sc.get(key)
        if hit is not None:
            ctx.metrics.bytes_scan_cache_read += host_slice.nbytes
            return hit
    dev = _device_put(host_slice, ctx)
    ctx.metrics.bytes_read_disk += host_slice.nbytes
    _scan_pool_put(ctx, key, dev, _reread_benefit(ctx, host_slice.nbytes))
    return dev


def _assemble(pieces: list, cap: int, like: jnp.ndarray) -> jnp.ndarray:
    """Concatenate partition arrays and zero-pad the row dim to cap."""
    total = sum(int(p.shape[0]) for p in pieces)
    pad = cap - total
    if pad:
        pieces = pieces + [jnp.zeros((pad,) + like.shape[1:], like.dtype)]
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, 0)


def _parts_assembled(ctx: ExecContext, st: "TableStorage", colname: str,
                     host_arr: np.ndarray, parts, ranges,
                     cap: int) -> jnp.ndarray:
    """Padded device column assembled from per-partition cache entries,
    with the ASSEMBLY itself memoized per (table, col, parts, cap) —
    repeat scans with the same prune set skip the device concat (the
    PR 1 warm-scan fast path), while the per-partition entries remain
    the shareable source tier for other prune sets.  Assembled entries
    carry a low benefit (rebuilding one is just a concat over resident
    pieces), so benefit-ranked eviction drops them before the pieces."""
    sc = ctx.scan_cache
    akey = (st.name, colname, "asm", tuple(parts), cap)
    if sc is not None:
        hit = sc.get(akey)
        if hit is not None:
            row_bytes = host_arr.nbytes // max(host_arr.shape[0], 1)
            live = sum(hi - lo for lo, hi in ranges)
            ctx.metrics.bytes_scan_cache_read += row_bytes * live
            return hit
    pieces = [_scan_part_cached(ctx, (st.name, colname, "part", p),
                                host_arr[lo:hi])
              for p, (lo, hi) in zip(parts, ranges) if hi > lo]
    arr = _assemble(pieces, cap, pieces[0] if pieces
                    else jnp.asarray(host_arr[:1]))
    if pieces and arr is pieces[0]:
        return arr      # identity assembly: already cached per-part
    # low benefit: rebuilding is one device concat over resident pieces
    nbytes = int(arr.size) * arr.dtype.itemsize
    _scan_pool_put(ctx, akey, arr, benefit=nbytes * 3e-10)
    return arr


def _exec_scan_partitioned(node: L.Scan, st: TableStorage,
                           info: PartitionInfo, ctx: ExecContext,
                           needed: Tuple[str, ...]) -> Table:
    """Scan a partitioned table: only the selected contiguous partition
    ranges are read, through per-partition device cache entries
    (ascending partition id, so the result is the unpruned relation
    with non-selected partitions' rows deleted, order preserved).

    With a multi-device ``ctx.sharding`` the selected ranges are
    assembled host-side and placed with the NamedSharding (rows — and
    hence partitions — spread across the mesh's devices); the assembled
    array is memoized per partition SET, trading cross-prune-set reuse
    for single-placement scans (ROADMAP: sharded-scan caveats).
    """
    parts = node.parts if node.parts is not None else info.all_parts()
    nrows = info.rows_of(parts)
    cap = next_pow2(max(nrows, 1))
    schema = st.schema.select(needed)
    if nrows == 0:       # every partition pruned (or restricted) away
        return Table(schema, empty_like(schema, cap), 0)
    ranges = [info.part_range(p) for p in parts]
    cols: Dict[str, jnp.ndarray] = {}

    def host_assembly(arr: np.ndarray):
        """Lazy host-side concat of the selected ranges (runs only on
        a scan-cache miss — warm sharded scans skip the memcpy) plus
        the live byte count for hit metrics."""
        if len(parts) == info.n_partitions:
            return (lambda: arr), arr.nbytes
        row_bytes = arr.nbytes // max(arr.shape[0], 1)
        live = sum(hi - lo for lo, hi in ranges)
        build = lambda: np.concatenate(
            [arr[lo:hi] for lo, hi in ranges if hi > lo], 0)
        return build, row_bytes * live

    sharded = ctx.sharding is not None
    if st.fmt == "csv":
        if sharded:
            build, live_bytes = host_assembly(st.csv_bytes)
            raw = _scan_cached(ctx, (st.name, "__csv__", parts),
                               build, cap, host_nbytes=live_bytes)
        else:
            raw = _parts_assembled(ctx, st, "__csv__", st.csv_bytes,
                                   parts, ranges, cap)
        offsets = st.schema.csv_offsets()
        for name in needed:
            off, w = offsets[name]
            fieldb = jax.lax.slice_in_dim(raw, off, off + w, axis=1)
            t = st.schema.coltype(name)
            ctx.metrics.bytes_parsed += nrows * w
            if t.kind == "i32":
                cols[name] = _parse_i32(fieldb)
            elif t.kind == "f32":
                cols[name] = _parse_f32(fieldb)
            else:
                cols[name] = fieldb
    else:
        for name in needed:
            src = st.columnar[name]
            if sharded:
                build, live_bytes = host_assembly(src)
                cols[name] = _scan_cached(ctx, (st.name, name, parts),
                                          build, cap,
                                          host_nbytes=live_bytes)
            else:
                cols[name] = _parts_assembled(ctx, st, name, src,
                                              parts, ranges, cap)
    return Table(schema, cols, nrows)


def _exec_scan(node: L.Scan, ctx: ExecContext,
               needed: Tuple[str, ...]) -> Table:
    st = ctx.catalog[node.table]
    if st.partitions is not None and st.partitions.n_partitions > 1:
        return _exec_scan_partitioned(node, st, st.partitions, ctx, needed)
    cap = next_pow2(st.nrows)
    cols: Dict[str, jnp.ndarray] = {}
    if st.fmt == "csv":
        # must read the WHOLE row bytes (CSV is row-oriented); only the
        # raw byte matrix is memoized — the parse/typecast still runs
        # per scan (it is the CSV format's intrinsic cost, and what the
        # paper's covering-expression cache exists to avoid)
        raw = _scan_cached(ctx, (st.name, "__csv__"), st.csv_bytes, cap)
        offsets = st.schema.csv_offsets()
        for name in needed:
            off, w = offsets[name]
            fieldb = jax.lax.slice_in_dim(raw, off, off + w, axis=1)
            t = st.schema.coltype(name)
            ctx.metrics.bytes_parsed += st.nrows * w
            if t.kind == "i32":
                cols[name] = _parse_i32(fieldb)
            elif t.kind == "f32":
                cols[name] = _parse_f32(fieldb)
            else:
                cols[name] = fieldb
    else:
        for name in needed:
            cols[name] = _scan_cached(ctx, (st.name, name),
                                      st.columnar[name], cap)
    schema = st.schema.select(needed)
    return Table(schema, cols, st.nrows)


def _est_cap(est: int, upper: int) -> int:
    """Power-of-two output capacity from a cardinality estimate."""
    cap = next_pow2(max(int(est * EST_HEADROOM), 1))
    return max(1, min(cap, next_pow2(max(upper, 1))))


def _to_host(ctx: ExecContext, x):
    """``x`` (device arrays, or a tuple of them) read on the host.  The
    read waits until the device has computed ``x``: one host sync,
    counted in ``ExecMetrics.host_syncs`` and spanned as ``exec.sync``."""
    ctx.metrics.host_syncs += 1
    with ctx.span("exec.sync"):
        return jax.device_get(x)


def _host_int(ctx: ExecContext, x) -> int:
    """A row count as a Python int: a device scalar is read on the host
    (:func:`_to_host`), a host int is taken as it is."""
    return int(_to_host(ctx, x)) if isinstance(x, jax.Array) else int(x)


def _deferred_dispatch(ctx: ExecContext, op: str, dispatch, est: int,
                       upper: int, count):
    """The deferred-sync pattern, shared by filter/join/aggregate and
    the fused pipeline: dispatch at the estimate-sized capacity BEFORE
    the host reads the true count, validate, and re-dispatch at the
    exact size only on estimate overflow.  ``upper`` bounds the
    *speculative* allocation (an overestimate must never allocate more
    than the operator could legitimately produce — or, for joins, a
    sane multiple of its inputs); the overflow re-dispatch uses the
    true count, which by then is known to be a real requirement.

    A large OVERestimate is also re-dispatched at the tight size (one
    pow2 step of slack is tolerated): the padded buffer would otherwise
    outlive the operator — returned as a query result or, worse,
    admitted to the CE cache at its padded nbytes, evicting entries the
    knapsack believed would fit.

    ``op`` names the operator in ``ExecMetrics.deferred_dispatches`` and
    ``redispatches``.  Returns (dispatch result, int count).
    """
    cap = _est_cap(est, upper)
    out = dispatch(cap)
    n = _host_int(ctx, count)
    tight = next_pow2(max(n, 1))
    redispatch = n > cap or cap > 2 * tight
    if redispatch:
        out = dispatch(tight)
    ctx.metrics.note_dispatch(op, redispatch)
    return out, n


def _exec_filter(pred: E.Expr, child: Table, ctx: ExecContext) -> Table:
    names = child.schema.names
    mask = count = None
    if ctx.use_pallas_filter:
        mask, count = _try_pallas_filter(pred, child)
    if mask is None:
        key = ("mask", E.canonical(pred), names, child.capacity)
        fn = _cached(key, lambda: _pred_mask_fn(key, pred, names))
        mask, count = fn(jnp.int32(child.nrows),
                         *[child.columns[n] for n in names])
    cols = [child.columns[n] for n in names]
    est = ctx.estimate("filter", pred, child.nrows)
    if est is not None:
        out, count = _deferred_dispatch(ctx, "filter",
                                        _Selection(mask, cols), est,
                                        child.capacity, count)
    else:
        count = _host_int(ctx, count)
        out = _compact(mask, next_pow2(max(count, 1)), *cols)
    ctx.metrics.rows_processed += child.nrows
    return Table(child.schema, dict(zip(names, out)), count)


def _exec_join(node: L.Join, left: Table, right: Table,
               ctx: ExecContext) -> Table:
    assert len(node.on) == 1, "single-key equi-joins (engine restriction)"
    lc, rc = node.on[0]
    if not left.schema.has(lc):
        lc, rc = rc, lc
    lk, rk = left.columns[lc], right.columns[rc]
    assert lk.dtype == jnp.int32, "join keys must be int32"

    # build side = right (sorted); probe = left.  Padding rows beyond
    # nrows hold stale values (compaction slack) — mask them to the
    # sentinel BEFORE sorting so rk_sorted is genuinely ascending and
    # searchsorted never matches padding.
    order, rk_sorted, kmin, kmax, dup = _join_build(
        rk, jnp.int32(right.nrows))
    # one host read of the build keys' range and uniqueness picks the
    # probe (a table over the range when it is no longer than the probe
    # side) and the expansion
    lo_key, hi_key, dup = _to_host(ctx, (kmin, kmax, dup))
    lo_key, hi_key = int(lo_key), int(hi_key)
    span = next_pow2(hi_key - lo_key + 1) if hi_key >= lo_key else 0
    if 0 < span <= lk.shape[0]:
        lo, m, total = _join_probe_dense(lk, rk_sorted,
                                         jnp.int32(left.nrows), kmin, kmax,
                                         span=span)
    else:
        lo, m, total = _join_probe(lk, rk_sorted, jnp.int32(left.nrows))

    if dup:
        def gather(out_cap: int) -> Dict[str, jnp.ndarray]:
            li, ri = _join_expand(lo, m, out_cap)
            out: Dict[str, jnp.ndarray] = {}
            for n in left.schema.names:
                out[n] = jnp.take(left.columns[n], li, axis=0)
            for n in right.schema.names:
                src = jnp.take(right.columns[n], order, axis=0)
                out[n] = jnp.take(src, ri, axis=0)
            return out
    else:
        # unique build keys (every FK->PK join): the matched probe rows
        # with their lo, sorted at most once per route (_Selection), so
        # a re-dispatch at the exact size only slices and gathers
        names = left.schema.names + right.schema.names
        sel = _Selection(m, [lo] + [left.columns[n]
                                    for n in left.schema.names])
        build_cols = [right.columns[n] for n in right.schema.names]

        def gather(out_cap: int) -> Dict[str, jnp.ndarray]:
            return dict(zip(names, _join_gather_unique(
                *sel.sort_for(out_cap), order, out_cap, len(sel.cols),
                *sel.cols, *build_cols)))
    ctx.metrics.note_join("expand" if dup else "unique")

    est = ctx.estimate("join", (lc, rc), left.nrows, right.nrows)
    if est is not None:
        # bound the speculative gather at a small multiple of the
        # larger input — a runaway NDV-based estimate (e.g. join keys
        # with no stats) must not allocate |L|x|R|-sized arrays; a true
        # output beyond the bound just takes the overflow re-gather.
        # With unique build keys no output exceeds the probe side.
        upper = (4 * max(left.nrows, right.nrows, 1) if dup
                 else max(left.nrows, 1))
        cols, total = _deferred_dispatch(ctx, "join", gather, est, upper,
                                         total)
    else:
        total = _host_int(ctx, total)
        cols = gather(next_pow2(max(total, 1)))
    ctx.metrics.rows_processed += left.nrows + right.nrows
    return Table(left.schema.concat(right.schema), cols, total)


_SEG_FNS = {
    "sum": jax.ops.segment_sum,
    "min": jax.ops.segment_min,
    "max": jax.ops.segment_max,
}


def _exec_aggregate(node: L.Aggregate, child: Table,
                    ctx: ExecContext) -> Table:
    n = child.capacity
    keys = [child.columns[g] for g in node.group_by]
    assert all(k.ndim == 1 for k in keys), "group keys must be scalar cols"

    order, gid, sorted_valid, n_groups = _agg_seg_ids(
        jnp.int32(child.nrows), *keys)

    est = ctx.estimate("group", node.group_by, child.nrows)
    cap = 1  # rebound by run_reduce before any trace reads it

    fns = tuple(fn for _, fn, _ in node.aggs)

    def make_reduce():
        def reduce_all(order, gid, sorted_valid, *vals):
            gid_c = jnp.where(sorted_valid, gid, cap)  # padding -> dropped
            outs = []
            for fn_name, v in zip(fns, vals):
                sv = jnp.take(v, order, axis=0)
                if fn_name == "count":
                    o = jax.ops.segment_sum(
                        sorted_valid.astype(jnp.int32), gid_c,
                        num_segments=cap)
                elif fn_name == "mean":
                    s = jax.ops.segment_sum(
                        jnp.where(sorted_valid, sv.astype(jnp.float32), 0.0),
                        gid_c, num_segments=cap)
                    c = jax.ops.segment_sum(
                        sorted_valid.astype(jnp.float32), gid_c,
                        num_segments=cap)
                    o = s / jnp.maximum(c, 1.0)
                elif fn_name in ("min", "max"):
                    big = jnp.asarray(
                        I32_SENTINEL if sv.dtype == jnp.int32 else jnp.inf,
                        sv.dtype)
                    fill = big if fn_name == "min" else (
                        -big if sv.dtype != jnp.int32 else -big - 1)
                    o = _SEG_FNS[fn_name](jnp.where(sorted_valid, sv, fill),
                                          gid_c, num_segments=cap)
                else:
                    o = jax.ops.segment_sum(
                        jnp.where(sorted_valid, sv,
                                  jnp.zeros((), sv.dtype)), gid_c,
                        num_segments=cap)
                outs.append(o)
            # first sorted row index of each group -> representative keys
            first = jax.ops.segment_min(
                jnp.where(sorted_valid, jnp.arange(n), n), gid_c,
                num_segments=cap)
            return tuple(outs), first

        return jax.jit(reduce_all)

    vals = tuple(child.columns[c if c else node.group_by[0]]
                 for _, fn, c in node.aggs)

    def run_reduce(cap_: int):
        nonlocal cap
        cap = cap_   # read by make_reduce's trace below
        reduce_key = ("agg_reduce", fns, cap_, n,
                      tuple(str(v.dtype) for v in vals))
        reduce_all = _cached(reduce_key, make_reduce)
        return reduce_all(order, gid, sorted_valid, *vals)

    if est is not None:
        # deferred sync: size the segment reduction from the NDV
        # estimate and dispatch it before reading the true group count;
        # group ids beyond the capacity are scatter-dropped, so an
        # underestimate only triggers the overflow re-reduce
        (outs, first), n_groups = _deferred_dispatch(
            ctx, "aggregate", run_reduce, est, child.nrows, n_groups)
    else:
        n_groups = _host_int(ctx, n_groups)
        outs, first = run_reduce(next_pow2(max(n_groups, 1)))

    cols: Dict[str, jnp.ndarray] = {}
    safe_first = jnp.minimum(first, n - 1)
    for g in node.group_by:
        sorted_col = jnp.take(child.columns[g], order, axis=0)
        cols[g] = jnp.take(sorted_col, safe_first, axis=0)
    for (out_name, fn, c), o in zip(node.aggs, outs):
        cols[out_name] = o
    ctx.metrics.rows_processed += child.nrows
    return Table(node.schema, cols, n_groups)


def _sort_fn(key, by_idx: int, in_cap: int, new_cap: int, desc: bool):
    """All sort output columns in ONE jitted call: sentinel-mask the
    key, stable argsort, gather every column through the same order,
    slice to ``new_cap``.  Valid rows sort ahead of the sentinel
    padding, so a slice of ``new_cap >= nrows`` keeps every live row
    (matching the eager path's live-row order bit for bit)."""
    def f(nrows, *cols):
        k = cols[by_idx]
        valid = jnp.arange(in_cap) < nrows
        if desc:
            k = -k
        k = jnp.where(valid, k, _sort_sentinel(k))
        sel = jnp.argsort(k, stable=True)[:new_cap]
        return tuple(jnp.take(c, sel, axis=0) for c in cols)

    return jax.jit(f)


def _exec_sort(node: L.Sort, child: Table, ctx: ExecContext) -> Table:
    names = child.schema.names
    est = ctx.estimate("sort", child.nrows)
    if est is not None:
        # deferred-sync path: the output capacity comes from the cost
        # model's cardinality estimate (exact for sort — cardinality is
        # preserved) instead of carrying the child's full padded
        # capacity forward, and every column is gathered inside one
        # jitted dispatch; the usual overflow guard recompacts if the
        # estimate ever lied
        by_idx = names.index(node.by)

        def dispatch(new_cap: int):
            fkey = ("sort", names, node.by, bool(node.desc),
                    child.capacity, new_cap,
                    str(child.columns[node.by].dtype))
            fn = _cached(fkey, lambda: _sort_fn(
                fkey, by_idx, child.capacity, new_cap, bool(node.desc)))
            return fn(jnp.int32(child.nrows),
                      *[child.columns[n] for n in names])

        outs, _ = _deferred_dispatch(ctx, "sort", dispatch, est,
                                     child.capacity, child.nrows)
        return Table(child.schema, dict(zip(names, outs)), child.nrows)

    # seed eager path: full-capacity order, one gather per column
    key = child.columns[node.by]
    if node.desc:
        key = jnp.where(jnp.arange(child.capacity) < child.nrows,
                        -key, _sort_sentinel(key))
        order = jnp.argsort(key, stable=True)
    else:
        order = _sort_order(key, jnp.int32(child.nrows), True)
    cols = {n: jnp.take(child.columns[n], order, axis=0)
            for n in child.schema.names}
    return Table(child.schema, cols, child.nrows)


def _union_fn(key, names: Tuple[str, ...], l_cap: int, r_cap: int,
              new_cap: int):
    """All union output columns in ONE jitted call: concat live-row
    masks and columns, one compaction (``_compact_nz_impl``) for every
    column (vs the seed's per-column argsort dispatches)."""
    k = len(names)

    def f(l_nrows, r_nrows, *cols):
        mask = jnp.concatenate([jnp.arange(l_cap) < l_nrows,
                                jnp.arange(r_cap) < r_nrows])
        return _compact_nz_impl(mask, new_cap, *(
            jnp.concatenate([lc, rc], axis=0)
            for lc, rc in zip(cols[:k], cols[k:])))

    return jax.jit(f)


def _exec_union(left: Table, right: Table, ctx: ExecContext) -> Table:
    total = left.nrows + right.nrows
    names = left.schema.names
    est = ctx.estimate("union", left.nrows, right.nrows)
    if est is not None:
        # deferred-sync path: output capacity from the sum of the input
        # cardinality estimates, one fused dispatch for every column;
        # the usual overflow guard recompacts if the estimate lied
        def dispatch(new_cap: int):
            key = ("union", names, left.capacity, right.capacity, new_cap)
            fn = _cached(key, lambda: _union_fn(key, names, left.capacity,
                                                right.capacity, new_cap))
            return fn(jnp.int32(left.nrows), jnp.int32(right.nrows),
                      *[left.columns[n] for n in names],
                      *[right.columns[n] for n in names])

        outs, total = _deferred_dispatch(
            ctx, "union", dispatch, est, left.capacity + right.capacity,
            total)
        return Table(left.schema, dict(zip(names, outs)), total)

    # seed eager path: exact-sized per-column argsort compaction
    cap = next_pow2(max(total, 1))
    cols = {}
    for name in names:
        a = left.columns[name][: left.capacity]
        b = right.columns[name][: right.capacity]
        mask = jnp.concatenate([
            jnp.arange(left.capacity) < left.nrows,
            jnp.arange(right.capacity) < right.nrows])
        merged = jnp.concatenate([a, b], axis=0)
        (compacted,) = _compact(mask, cap, merged)
        cols[name] = compacted
    return Table(left.schema, cols, total)


# column kinds the Pallas filter kernel reads (see _try_pallas_filter)
_KERNEL_KINDS = ("i32", "f32")


def _try_pallas_filter(pred: E.Expr, child: Table):
    """Route a numeric predicate through the fused filter-scan kernel.
    Returns (mask, count) or (None, None) when unsupported (string
    predicates stay on the XLA path; numeric col-col compares and
    fractional thresholds on integer columns compile — see
    kernels.filter_project.ops.compile_predicate)."""
    from ..kernels.filter_project.ops import compile_predicate, filter_mask

    # i64 columns stay off the kernel route: Mosaic has no 64-bit lane,
    # so a predicate reading one raises KeyError here and takes XLA
    numeric = tuple(n for n, t in child.schema.fields
                    if t.kind in _KERNEL_KINDS)
    try:
        program = compile_predicate(pred, numeric)
    except (ValueError, KeyError):
        return None, None
    cols = tuple(child.columns[n] for n in numeric)
    block = min(2048, child.capacity)
    return filter_mask(cols, program, child.nrows, block=block)


# ---------------------------------------------------------------------------
# multi-device sharded scans: per-shard predicate evaluation
# ---------------------------------------------------------------------------
def _sharded_mask_fn(key, pred: E.Expr, names: Tuple[str, ...],
                     ndims: Tuple[int, ...], mesh, axis: str):
    """Predicate mask per shard under shard_map: each device evaluates
    its local rows (embarrassingly parallel — the fused filter's row
    scan runs on every device at once), the count is one psum, and the
    mask comes back row-sharded for the global compaction that follows
    (compaction is data-dependent-shape and stays in XLA/GSPMD)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def local(nrows, *cols):
        n_local = cols[0].shape[0]
        base = jax.lax.axis_index(axis) * n_local
        columns = dict(zip(names, cols))
        live = (base + jnp.arange(n_local)) < nrows
        mask = E.eval_expr(pred, columns) & live
        count = jax.lax.psum(jnp.sum(mask.astype(jnp.int32)), axis)
        return mask, count

    in_specs = (P(),) + tuple(
        P(axis) if nd == 1 else P(axis, None) for nd in ndims)
    fn = shard_map(local, mesh=mesh, in_specs=in_specs,
                   out_specs=(P(axis), P()), check_vma=False)
    return jax.jit(fn)


def _try_shard_map_mask(pred: E.Expr, child: Table, ctx: ExecContext):
    """(mask, count) via per-shard evaluation, or (None, None) when the
    context is not multi-device row-sharded (single-axis NamedSharding
    with the row capacity divisible by the axis size)."""
    sh = ctx.sharding
    if not isinstance(sh, jax.sharding.NamedSharding):
        return None, None
    spec = tuple(sh.spec)
    if not spec or not isinstance(spec[0], str):
        return None, None
    axis = spec[0]
    mesh = sh.mesh
    n_sh = dict(zip(mesh.axis_names, mesh.devices.shape)).get(axis, 1)
    if n_sh <= 1 or child.capacity % n_sh:
        return None, None
    names = child.schema.names
    ndims = tuple(child.columns[n].ndim for n in names)
    key = ("smask", E.canonical(pred), names, child.capacity,
           axis, n_sh, str(sh))
    fn = _cached(key, lambda: _sharded_mask_fn(key, pred, names, ndims,
                                               mesh, axis))
    return fn(jnp.int32(child.nrows), *[child.columns[n] for n in names])


# ---------------------------------------------------------------------------
# fused pipelines (relational.fuse): leaf → Filter* → Project in ONE call
# ---------------------------------------------------------------------------
def _fused_fn(key, pred: E.Expr, in_names: Tuple[str, ...],
              out_cols: Tuple[str, ...], new_cap: int):
    """mask + count + compact + project as a single jitted function;
    the sorted selection comes out too (``keys``, ``carried``), for a
    re-dispatch at the exact size that does not sort again."""
    def f(nrows, *cols):
        columns = dict(zip(in_names, cols))
        n = cols[0].shape[0]
        mask = E.eval_expr(pred, columns) & (jnp.arange(n) < nrows)
        count = jnp.sum(mask.astype(jnp.int32))
        outs = tuple(columns[c] for c in out_cols)
        keys, carried = _sort_selection(mask, outs, _carries(n, new_cap))
        return (mask, count, keys, carried,
                _head_selected(keys, carried, outs, new_cap))
    return jax.jit(f)


def _slot_compile(pred: E.Expr, schema):
    """Slotted compile of ``pred`` over the schema's numeric predicate
    columns.  Returns (program, ivals, fvals, names) or None when the
    predicate falls off the slotted route (string compares, col-col over
    strings, out-of-range consts...)."""
    from ..kernels.filter_project.ops import compile_predicate_slots

    kinds = {n: t.kind for n, t in schema.fields}
    pcols = E.columns_of(pred)
    names = tuple(n for n in schema.names
                  if n in pcols and kinds[n] in ("i32", "i64", "f32"))
    if not names:
        return None
    try:
        program, ivals, fvals = compile_predicate_slots(pred, names, kinds)
    except (ValueError, KeyError):
        return None
    return program, ivals, fvals, names


def _kernel_reads(names, schema) -> bool:
    """Can the Pallas kernel read every one of these columns?"""
    return all(schema.coltype(n).kind in _KERNEL_KINDS for n in names)


def _count_route(ctx: ExecContext, use_pallas: bool) -> None:
    if use_pallas:
        ctx.metrics.pallas_dispatches += 1
    else:
        ctx.metrics.xla_slotted_dispatches += 1


def _slotted_mask(pred: E.Expr, child: Table, ctx: ExecContext,
                  use_pallas: bool):
    """Per-query mask+count through the SLOTTED program route: the
    jitted fn is keyed by plan shape (literals live in operand arrays),
    so recurring templates with fresh constants never re-trace.  This is
    exactly a batch of one — bit-identical to a window-batched dispatch
    of the same plan.  Returns (mask, count) or (None, None)."""
    from ..kernels.filter_project.ops import filter_mask_batch, pack_consts

    compiled = _slot_compile(pred, child.schema)
    if compiled is None:
        return None, None
    program, ivals, fvals, names = compiled
    if use_pallas and not _kernel_reads(names, child.schema):
        return None, None
    ic, fc = pack_consts([ivals], [fvals])
    block = min(2048, child.capacity)
    key = ("slotmask", program, names, 1, child.capacity, block,
           use_pallas)
    fn = _shape_cached(ctx, key, lambda: partial(
        filter_mask_batch, block=block, use_pallas=use_pallas))
    cols = tuple(child.columns[n] for n in names)
    mask, counts = fn(cols, program, jnp.int32(child.nrows), ic, fc)
    _count_route(ctx, use_pallas)
    return mask[0], counts[0]


def _fused_est(src, pred: E.Expr, child: Table, est_rows: Optional[int],
               ctx: ExecContext) -> Optional[int]:
    """The fused pipeline's deferred-sync output-capacity estimate
    (shared verbatim by the per-query and window-batched routes, so a
    batched member sizes its compaction exactly like a solo run)."""
    est = ctx.estimate("filter", pred,
                       est_rows if est_rows is not None else child.nrows)
    if est is not None and est_rows is not None:
        est = min(est, child.nrows)
    if (est is not None and isinstance(src, L.Scan)
            and src.parts is not None):
        # partition-RESTRICTED scan (per-partition CE recompute): the
        # restriction exists because the covering predicate keeps these
        # partitions, so whole-table selectivity applied to partition
        # rows systematically undershoots (range partitioning on the
        # filter column is the worst case: every row passes) — forcing
        # the overflow re-dispatch on the warm recompute path.  Size at
        # the partition input; the overshoot guard recompacts the rare
        # genuinely-selective case.
        est = child.nrows
    if est is not None and isinstance(src, L.CachedScan):
        # residual over a covering relation: condition on the covering
        # plan's selectivity (the CE output already passed the OR of
        # member predicates, so base-table selectivities undershoot)
        cov = ctx.cache_plans.get(src.psi)
        sel_fn = getattr(ctx.cost_model, "plan_selectivity", None)
        if cov is not None and sel_fn is not None:
            est = min(child.nrows, int(est / sel_fn(cov)))
    return est


def _pruned_scan(ctx: ExecContext, src: L.Scan, st: "TableStorage",
                 pred: E.Expr):
    """Resolve the live partitions of a fused scan+filter: the
    conservative stats pruner first, then intersection with resident
    pid bitsets — observed history composes with, never overrides,
    statistics (PR 8).  The deferred-sync capacity estimate stays taken
    over the FULL table (the qualifying rows all live in surviving
    partitions — estimating over the pruned input would undershoot by
    exactly the pruned fraction and force the overflow recompact on the
    hot path), then capped at the pruned input size by the caller.

    This is also the ``pid_pool`` fault point: the bitset read is
    attempted for EVERY fused scan+filter (an unpartitioned table is
    just a one-partition layout whose read trivially finds nothing),
    and any failure in the pid path — injected or real — degrades to
    stats-only pruning with a :class:`DegradationEvent` instead of
    surfacing.  A pid hit is an optimization, never a failure domain.

    Returns ``(resolved src, est_rows, pid_scan)``; ``pid_scan`` is
    ``(table, PartitionInfo, scanned parts)`` when the row mask this
    scan produces is eligible for presence recording (the scan started
    unrestricted, so absent-from-mask == empty-for-pred over the whole
    table), else None.
    """
    info = st.partitions
    partitioned = (ctx.prune and info is not None
                   and info.n_partitions > 1)
    live = prune_parts(pred, info) if partitioned else None
    if ctx.pid_cache is not None:
        try:
            ctx.check_fault("pid_pool", key=src.table)
            if partitioned:
                key = E.canonical(pred)
                live2, hits = ctx.pid_cache.intersect(
                    src.table, key, pred, info.n_partitions, live,
                    implies=lambda p, q, _s=st.schema:
                        _subsumes(p, q, _s))
                ctx.metrics.pid_hits += hits
                dropped = len(live) - len(live2)
                if dropped > 0:
                    ctx.metrics.pid_pruned_parts += dropped
                    # per-(table, pred) the drop count is deterministic
                    # within a window: assign, don't accumulate
                    ctx.pid_prune_log[(src.table, key)] = dropped
                    live = live2
        except Exception as exc:
            ctx.degradations.append(DegradationEvent(
                query=-1, attempt=1, action="degrade",
                level="stats-prune", error=repr(exc),
                detail={"point": "pid_pool", "table": src.table}))
    if not partitioned:
        return src, None, None
    est_rows = None
    if len(live) < info.n_partitions:
        from dataclasses import replace as _dc_replace

        src = _dc_replace(src, parts=tuple(live))
        est_rows = st.nrows
    scanned = src.parts if src.parts is not None else info.all_parts()
    return src, est_rows, (src.table, info, scanned)


def _pid_record(ctx: ExecContext, pid_scan, pred: E.Expr, mask,
                nrows: int) -> None:
    """Record the observed presence bitset for ``(table, pred)`` as a
    side effect of an eligible fused execution.  Record-once: the host
    read of ``mask`` synchronizes the device, so a key already resident
    is skipped before touching the array — warm streams pay nothing
    here.  Failures degrade to not-recording (never to the query)."""
    pool = ctx.pid_cache
    if pool is None or pid_scan is None or mask is None:
        return
    table_name, info, parts = pid_scan
    try:
        key = E.canonical(pred)
        if pool.contains(table_name, key):
            return
        host = _to_host(ctx, mask)[:nrows]
        present = pid_presence_from_mask(host, info, parts)
        pool.record(table_name, key, pred, info.n_partitions, present)
        ctx.metrics.pid_records += 1
    except Exception as exc:
        ctx.degradations.append(DegradationEvent(
            query=-1, attempt=1, action="degrade", level="no-record",
            error=repr(exc),
            detail={"point": "pid_pool", "table": table_name}))


def _exec_fused(node: FusedPipeline, ctx: ExecContext) -> Table:
    # covers the Pallas and fused-XLA routes; the eager per-operator
    # path (the degradation ladder's bottom rung) never dispatches here
    ctx.check_fault("kernel_launch")
    src, pred = node.source, node.pred
    need = set(node.cols) | E.columns_of(pred)
    est_rows = None
    pid_scan = None
    if isinstance(src, L.Scan):
        st = ctx.catalog[src.table]
        if src.parts is None and not isinstance(pred, E.TrueExpr):
            # partition pruning: statistics (then resident pid bitsets)
            # refute the predicate on the skipped partitions, so the
            # scan reads only the surviving contiguous ranges
            src, est_rows, pid_scan = _pruned_scan(ctx, src, st, pred)
        needed = tuple(n for n in src.schema.names if n in need)
        child = _exec_scan(src, ctx, needed)
    else:
        table = _cached_scan_table(src, ctx)
        child = table.select([n for n in src.schema.names
                              if n in need and table.schema.has(n)])

    if isinstance(pred, E.TrueExpr):
        return child.select(node.cols)

    in_names = child.schema.names
    in_cols = [child.columns[n] for n in in_names]
    est = _fused_est(src, pred, child, est_rows, ctx)
    out_schema = node.schema

    mask = count = None
    if ctx.use_pallas_filter:
        # kernel computes mask+count; only the data-dependent-shape
        # compaction stays in XLA (see kernels.filter_project.kernel).
        # Shape-cached slotted program first (no re-trace on fresh
        # literals), legacy literal program as fallback.
        if ctx.shape_cache:
            mask, count = _slotted_mask(pred, child, ctx, use_pallas=True)
        if mask is None:
            mask, count = _try_pallas_filter(pred, child)
    if mask is None:
        # multi-device row sharding: predicate evaluation per shard
        # under shard_map (no communication except the count psum)
        mask, count = _try_shard_map_mask(pred, child, ctx)
    if mask is None and ctx.shape_cache:
        # fused-XLA slotted route: same shape-keyed program, evaluated
        # by the jitted batch oracle instead of the Pallas kernel
        mask, count = _slotted_mask(pred, child, ctx, use_pallas=False)

    out_cols = [child.columns[c] for c in node.cols]
    if mask is not None:
        if est is not None:
            outs, count = _deferred_dispatch(
                ctx, "project", _Selection(mask, out_cols), est,
                child.capacity, count)
        else:
            count = _host_int(ctx, count)
            outs = _compact_nz(mask, next_pow2(max(count, 1)), *out_cols)
    elif est is not None:
        # single dispatch: mask, count and the projected compaction all
        # come out of one jitted call sized by the estimate
        new_cap = _est_cap(est, child.capacity)
        key = ("fused", E.canonical(pred), in_names, node.cols,
               child.capacity, new_cap)
        fn = _cached(key, lambda: _fused_fn(key, pred, in_names,
                                            node.cols, new_cap))
        mask, count, keys, carried, outs = fn(jnp.int32(child.nrows),
                                              *in_cols)
        count = _host_int(ctx, count)
        tight = next_pow2(max(count, 1))
        redispatch = count > new_cap or new_cap > 2 * tight
        if redispatch:
            # estimate overflow (or gross overshoot): recompact exactly
            outs = _Selection(mask, out_cols, keys, carried)(tight)
        ctx.metrics.note_dispatch("project", redispatch)
    else:
        # no estimator: two dispatches, but still no intermediate
        # relation — only the output columns are ever compacted
        key = ("mask", E.canonical(pred), in_names, child.capacity)
        fn = _cached(key, lambda: _pred_mask_fn(key, pred, in_names))
        mask, count = fn(jnp.int32(child.nrows), *in_cols)
        count = _host_int(ctx, count)
        outs = _compact_nz(mask, next_pow2(max(count, 1)), *out_cols)

    _pid_record(ctx, pid_scan, pred, mask, child.nrows)
    ctx.metrics.rows_processed += child.nrows
    return Table(out_schema, dict(zip(node.cols, outs)), count)


# ---------------------------------------------------------------------------
# window-batched execution: same-shape fused pipelines -> ONE dispatch
# ---------------------------------------------------------------------------
@dataclass
class _BatchMember:
    """One window query admitted to a batched dispatch group."""
    pos: int                      # caller's window position
    node: FusedPipeline
    src: L.Node                   # prune-resolved source leaf
    need: frozenset               # scan columns (output + predicate)
    est_rows: Optional[int]       # pre-prune row count for estimation
    program: tuple                # slotted postfix program (the shape)
    ivals: tuple
    fvals: tuple
    pred_names: Tuple[str, ...]   # numeric predicate columns, schema order
    # (table, PartitionInfo, scanned parts) when this member's row mask
    # is eligible for pid-bitset presence recording (see _pruned_scan)
    pid_scan: Optional[tuple] = None


def plan_window_batches(plans, ctx: ExecContext):
    """Group a closed window's plans for batched kernel execution.

    ``plans`` is a sequence of ``(pos, logical plan)`` pairs.  A plan is
    batch-capable when it fuses to a FusedPipeline whose predicate
    compiles to a slotted program; plans sharing (source leaf, program
    shape, predicate columns) — i.e. literal variants of one template
    over one table — land in the same group and will evaluate as ONE
    batched mask dispatch.  Returns ``(n_candidates, groups)`` where
    groups have >= 2 members (singletons stay on the per-query path) and
    the cost model has priced the shared dispatch below per-query ones.
    """
    if not ctx.fuse or not ctx.shape_cache:
        return 0, []
    from dataclasses import replace as _dc_replace

    buckets: Dict[tuple, list] = {}
    n_cand = 0
    for pos, plan in plans:
        node = fuse_plan(L.as_node(plan))
        if not isinstance(node, FusedPipeline):
            continue
        pred = node.pred
        if isinstance(pred, E.TrueExpr):
            continue
        src = node.source
        est_rows = None
        pid_scan = None
        if isinstance(src, L.Scan):
            st = ctx.catalog.get(src.table)
            if st is None:
                continue
            if src.parts is None:
                # resolve pruning (stats + pid bitsets) NOW so the
                # group key reflects the actual scanned ranges (members
                # with different live partition sets must not share a
                # mask dispatch)
                src, est_rows, pid_scan = _pruned_scan(ctx, src, st,
                                                       pred)
            leaf = ("scan", src.table, src.parts, st.fmt)
        elif isinstance(src, L.CachedScan):
            leaf = ("cs", src.psi)
        else:
            continue
        compiled = _slot_compile(pred, src.schema)
        if compiled is None:
            continue
        program, ivals, fvals, pred_names = compiled
        n_cand += 1
        key = (leaf, program, pred_names)
        buckets.setdefault(key, []).append(_BatchMember(
            pos=pos, node=node, src=src,
            need=frozenset(node.cols) | E.columns_of(pred),
            est_rows=est_rows, program=program, ivals=ivals,
            fvals=fvals, pred_names=pred_names, pid_scan=pid_scan))

    groups = []
    wd = getattr(ctx.cost_model, "window_dispatch_cost", None) \
        if ctx.cost_model is not None else None
    for ms in buckets.values():
        if len(ms) < 2:
            continue
        if wd is not None and wd(len(ms), batched=True) >= \
                wd(len(ms), batched=False):
            continue
        groups.append(ms)
    return n_cand, groups


def _prepare_group(members, ctx: ExecContext):
    """Phase one of a group: per-member scans + the ONE batched
    mask/count dispatch (async — nothing here blocks on the device)."""
    from ..kernels.filter_project.ops import filter_mask_batch, pack_consts

    children = []
    for m in members:
        src = m.src
        if isinstance(src, L.Scan):
            needed = tuple(n for n in src.schema.names if n in m.need)
            children.append(_exec_scan(src, ctx, needed))
        else:
            table = _cached_scan_table(src, ctx)
            children.append(table.select(
                [n for n in src.schema.names
                 if n in m.need and table.schema.has(n)]))
    base = children[0]
    for ch in children[1:]:
        if ch.capacity != base.capacity or ch.nrows != base.nrows:
            raise RuntimeError("window-batch group children diverge")
    names = members[0].pred_names
    # predicate columns come from the FIRST member's child — same leaf,
    # same device buffers (scan cache), so no member pays a second scan
    cols = tuple(base.columns[n] for n in names)
    # pad the member dimension to a power of two so realized group
    # sizes bucket into few compile shapes (a serving window closes
    # with whatever arrived — without padding every distinct size
    # recompiles the batch kernel).  Padded rows duplicate member 0's
    # literals; their mask/count rows are never read, and real members'
    # rows are computed independently of them (bit-identical).
    n_pad = next_pow2(len(members))
    fill = [members[0]] * (n_pad - len(members))
    ic, fc = pack_consts([m.ivals for m in members + fill],
                         [m.fvals for m in members + fill])
    block = min(2048, base.capacity)
    use_pallas = (ctx.use_pallas_filter
                  and _kernel_reads(names, base.schema))
    key = ("slotmask", members[0].program, names, n_pad,
           base.capacity, block, use_pallas)
    fn = _shape_cached(ctx, key, lambda: partial(
        filter_mask_batch, block=block, use_pallas=use_pallas))
    mask, counts = fn(cols, members[0].program, jnp.int32(base.nrows),
                      ic, fc)
    _count_route(ctx, use_pallas)
    ctx.metrics.batched_dispatches += 1
    ctx.metrics.batched_queries += len(members)
    return children, mask, counts


def _finalize_group(members, prep, ctx: ExecContext):
    """Phase two: blocking count reads + per-member deferred-sync
    compactions (identical sizing to the solo ``_exec_fused`` path, so
    batched results are bit-identical to per-query dispatch)."""
    children, mask, counts = prep
    outs = []
    for q, (m, child) in enumerate(zip(members, children)):
        est = _fused_est(m.src, m.node.pred, child, m.est_rows, ctx)
        mrow = mask[q]
        crow = counts[q]
        out_cols = [child.columns[c] for c in m.node.cols]
        if est is not None:
            cols_out, count = _deferred_dispatch(
                ctx, "project", _Selection(mrow, out_cols), est,
                child.capacity, crow)
        else:
            count = _host_int(ctx, crow)
            cols_out = _compact_nz(mrow, next_pow2(max(count, 1)),
                                   *out_cols)
        _pid_record(ctx, m.pid_scan, m.node.pred, mrow, child.nrows)
        ctx.metrics.rows_processed += child.nrows
        outs.append(Table(m.node.schema,
                          dict(zip(m.node.cols, cols_out)), count))
    return outs


def execute_window_batched(groups, ctx: ExecContext):
    """Run planned groups: phase one dispatches EVERY group's scans and
    batched mask kernels before phase two reads any count — JAX's async
    dispatch overlaps the remaining host-side pad/copy work with device
    compute already in flight.  A failing group degrades whole (its
    members return to the caller's per-query path); per-member results
    carry an even split of the group's wall time.

    Returns ``(results {pos: Table}, seconds {pos: float},
    failures {pos: Exception})``.
    """
    results: Dict[int, Table] = {}
    seconds: Dict[int, float] = {}
    failures: Dict[int, Exception] = {}
    with ctx.span("dispatch.batched", n_groups=len(groups),
                  n_queries=sum(len(g) for g in groups)):
        prepped = []
        for g in groups:
            t0 = time.perf_counter()
            try:
                prepped.append((g, _prepare_group(g, ctx),
                                time.perf_counter() - t0))
            except Exception as exc:
                for m in g:
                    failures[m.pos] = exc
        for g, prep, dt0 in prepped:
            t0 = time.perf_counter()
            try:
                with ctx.span("dispatch.batched.finalize",
                              n_members=len(g)):
                    outs = _finalize_group(g, prep, ctx)
                    for t in outs:
                        jax.block_until_ready(list(t.columns.values()))
            except Exception as exc:
                for m in g:
                    failures[m.pos] = exc
                continue
            per = (dt0 + time.perf_counter() - t0) / len(g)
            for m, t in zip(g, outs):
                results[m.pos] = t
                seconds[m.pos] = per
    return results, seconds, failures


# ---------------------------------------------------------------------------
# the interpreter
# ---------------------------------------------------------------------------
def execute(node: L.Node, ctx: ExecContext) -> Table:
    from .stats import required_columns

    node = L.as_node(node)
    if ctx.fuse:
        node = fuse_plan(node)
    req = required_columns(node)
    return _exec(node, ctx, req)


def _exec(node: L.Node, ctx: ExecContext, req) -> Table:
    if isinstance(node, FusedPipeline):
        out = _exec_fused(node, ctx)
    elif isinstance(node, L.Scan):
        needed = req.get(id(node), frozenset(node.schema.names))
        ordered = tuple(n for n in node.schema.names if n in needed)
        out = _exec_scan(node, ctx, ordered)
    elif isinstance(node, L.CachedScan):
        out = _exec_cached_scan(node, ctx, req)
    elif isinstance(node, L.Filter):
        child = _exec(node.child, ctx, req)
        out = _exec_filter(node.pred, child, ctx)
    elif isinstance(node, L.Project):
        child = _exec(node.child, ctx, req)
        out = child.select([c for c in node.cols if child.schema.has(c)])
    elif isinstance(node, L.Join):
        left = _exec(node.left, ctx, req)
        right = _exec(node.right, ctx, req)
        out = _exec_join(node, left, right, ctx)
    elif isinstance(node, L.Aggregate):
        child = _exec(node.child, ctx, req)
        out = _exec_aggregate(node, child, ctx)
    elif isinstance(node, L.Sort):
        child = _exec(node.child, ctx, req)
        out = _exec_sort(node, child, ctx)
    elif isinstance(node, L.Limit):
        child = _exec(node.child, ctx, req)
        new_n = min(node.n, child.nrows)
        cap = next_pow2(max(new_n, 1))
        cols = {n: child.columns[n][:cap] for n in child.schema.names}
        out = Table(child.schema, cols, new_n)
    elif isinstance(node, L.Union):
        left = _exec(node.left, ctx, req)
        right = _exec(node.right, ctx, req)
        out = _exec_union(left, right, ctx)
    elif isinstance(node, L.Cache):
        out = _materialize_cache(node, ctx, req)
    else:
        raise TypeError(type(node))
    return out


def _concat_tables(schema: Schema, tables: list) -> Table:
    """Stack partition outputs (ascending partition id) into one
    relation: live rows of each piece, concatenated, padded to pow2."""
    total = sum(t.nrows for t in tables)
    cap = next_pow2(max(total, 1))
    if total == 0:
        return Table(schema, empty_like(schema, cap), 0)
    cols: Dict[str, jnp.ndarray] = {}
    for name in schema.names:
        pieces = [t.columns[name][: t.nrows] for t in tables if t.nrows]
        cols[name] = _assemble(pieces, cap, pieces[0])
    return Table(schema, cols, total)


def _partitioned_ce_table(psi: bytes, ctx: ExecContext) -> Table:
    """A partition-grained CE's full output: resident partitions come
    from the cache, cold partitions re-run the covering plan restricted
    to that partition (admitted ones are materialized as they compute).
    Composition order is ascending partition id — the same order an
    unpartitioned materialization would produce.  Admissions run inside
    one cache transaction: a failure part-way through the partition
    loop rolls back the partitions this call already admitted, so the
    pool budget never leaks on a partial multi-entry admission."""
    composed = ctx.ce_part_memo.get((psi, "composed"))
    if composed is not None:
        # one composition per window: every consumer reads the same
        # Table (matching the whole-CE path's materialize-once shape)
        return composed
    pp = ctx.partitioned_ces[psi]
    pieces = []
    txn = ctx.cache.transaction() if ctx.cache is not None else None
    try:
        for pid in pp.live:
            cached = ctx.cache.get((psi, pid)) if ctx.cache is not None \
                else None
            if cached is not None:
                ctx.metrics.bytes_cached_read += cached.nbytes
                pieces.append(cached)
                continue
            memo = ctx.ce_part_memo.get((psi, pid))
            if memo is not None:
                pieces.append(memo)
                continue
            plan = restrict_to_parts(pp.plan, (pid,))
            if ctx.fuse:
                plan = fuse_plan(plan)
            t = _exec(plan, ctx, required_columns_of(plan))
            if txn is not None and pid in pp.admitted:
                ctx.check_fault("ce_admission", key=(psi, pid))
                txn.put((psi, pid), t, nbytes=t.nbytes,
                        est_bytes=t.logical_nbytes,
                        benefit=pp.benefits.get(pid, 0.0))
            else:
                ctx._memo_put((psi, pid), t)
            pieces.append(t)
    except Exception:
        if txn is not None:
            txn.rollback()
        raise
    if txn is not None:
        txn.commit()
    out = _concat_tables(pp.plan.schema, pieces)
    # prefer memoizing the composed table (later reads are then free);
    # it subsumes the per-partition entries, so release those on
    # success.  Under a tight budget the composed copy may not fit the
    # memo allowance — keep the (smaller) cold pieces instead and let
    # later reads re-concat from cache + memo.
    for pid in pp.live:
        ctx._memo_drop((psi, pid))
    if not ctx._memo_put((psi, "composed"), out):
        for pid, t in zip(pp.live, pieces):
            if ctx.cache is None or not ctx.cache.contains((psi, pid)):
                ctx._memo_put((psi, pid), t)
    return out


def _record_calibration(ctx: ExecContext, kind: str, psi: bytes, plan,
                        seconds: float, table: Table) -> None:
    """Cost-model accuracy accounting: one predicted-vs-measured sample
    per CE materialization / cached read, fed to the session's
    :class:`~repro.core.costmodel.CalibrationLog` (PR 9).  Best-effort —
    a model that can't price the plan just skips the sample."""
    tel = ctx.telemetry
    cm = ctx.cost_model
    if tel is None or cm is None:
        return
    try:
        if kind == "materialize":
            predicted = cm.execution_cost(plan) + cm.write_cost(plan)
        else:
            predicted = cm.read_cost(plan)
        sample = CalibrationSample(
            kind=kind, key=psi.hex()[:12],
            predicted_cost=float(predicted),
            measured_seconds=float(seconds),
            predicted_bytes=int(cm.output_bytes(plan)),
            measured_bytes=int(table.nbytes),
            predicted_rows=int(cm.output_rows(plan)),
            measured_rows=int(table.nrows))
    except Exception:
        return
    tel.calibration.record(sample)


def _materialize_cache(node: L.Cache, ctx: ExecContext, req) -> Table:
    assert ctx.cache is not None, "cache plan requires a CacheManager"
    existing = ctx.cache.get(node.psi)
    if existing is not None:
        # a WHOLE resident entry serves even when this window treats
        # the CE as partition-grained: eligibility for partitioning
        # depends on the other CEs in the window, so the same content
        # can be admitted whole in one window and per-partition in the
        # next — the already-materialized bytes must not be recomputed
        return existing
    if node.psi in ctx.failed_ces:
        raise CEMaterializationError(node.psi)
    try:
        if node.psi in ctx.partitioned_ces:
            return _partitioned_ce_table(node.psi, ctx)
        t0 = time.perf_counter()
        with ctx.span("ce.materialize", psi=node.psi):
            table = _exec(node.child, ctx, req)
            # the calibration sample times the materialization to the
            # device's end, not to the last dispatch
            jax.block_until_ready(list(table.columns.values()))
            ctx.check_fault("ce_admission", key=node.psi)
            ctx.cache.put(node.psi, table, nbytes=table.nbytes,
                          est_bytes=table.logical_nbytes,
                          benefit=ctx.cache_values.get(node.psi, 0.0))
        _record_calibration(ctx, "materialize", node.psi, node.child,
                            time.perf_counter() - t0, table)
    except CEMaterializationError:
        raise
    except Exception as exc:
        ctx.failed_ces.add(node.psi)
        raise CEMaterializationError(node.psi, exc) from exc
    return table


def _cached_scan_table(node: L.CachedScan, ctx: ExecContext) -> Table:
    """The full covering relation behind a CachedScan (materializing on
    first touch: Spark cache() is a transformation — §6.3 footnote 5)."""
    assert ctx.cache is not None
    t0 = time.perf_counter()
    table = ctx.cache.get(node.psi)
    if table is not None:
        # whole resident entry — serves even if this window re-planned
        # the CE as partition-grained (see _materialize_cache)
        ctx.metrics.bytes_cached_read += table.nbytes
        if ctx.telemetry is not None:
            plan = ctx.cache_plans.get(node.psi)
            if plan is not None:
                _record_calibration(ctx, "cached_read", node.psi, plan,
                                    time.perf_counter() - t0, table)
        return table
    if node.psi in ctx.failed_ces:
        # poisoned earlier this window: fail fast so the service reruns
        # this consumer on its residual plan instead of recomputing the
        # covering union inline
        raise CEMaterializationError(node.psi)
    try:
        if node.psi in ctx.partitioned_ces:
            return _partitioned_ce_table(node.psi, ctx)
        plan = ctx.cache_plans.get(node.psi)
        if plan is None:
            raise KeyError(f"no cache plan registered for ψ="
                           f"{node.psi.hex()[:12]}")
        if ctx.fuse:
            plan = fuse_plan(plan)
        return _exec(plan, ctx, required_columns_of(plan))
    except CEMaterializationError:
        raise
    except Exception as exc:
        ctx.failed_ces.add(node.psi)
        raise CEMaterializationError(node.psi, exc) from exc


def _exec_cached_scan(node: L.CachedScan, ctx: ExecContext, req) -> Table:
    table = _cached_scan_table(node, ctx)
    # present the cached covering relation under this node's schema
    return table.select([n for n in node.schema.names
                         if n in table.schema.names])


def required_columns_of(plan: L.Node):
    from .stats import required_columns

    return required_columns(plan)
