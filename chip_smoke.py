"""End-to-end smoke of the online query service on one TPU chip.

Drives the main path once, through the entry points a user calls: the
TPC-DS-analog catalog at scale factor 10 (``store_sales`` = 28,800,991
rows, columnar) is registered in a ``Session``; all 50 ``tpcds_queries``
are submitted to a ``QueryService`` twice (cold, then warm); every
window is optimized by the MQO/MCKP and runs fused scan -> filter ->
project pipelines through the Pallas filter kernel.  The run fails when

  * a handle fails, or the warm pass reads no resident covering
    expression or runs no window-batched dispatch;
  * a result differs from ``Session.run_batch(..., mqo=False)`` or from
    a plain vectorized NumPy reference of the same query;
  * any degradation or fault counter is non-zero, or a kernel-eligible
    predicate ran anywhere but on the Pallas kernel.

Run:  python chip_smoke.py             (one chip)
      python chip_smoke.py --chips 4   (sharded-scan phase only, SF1)

Earlier lines carry per-phase summaries; wall times there are set-up
times, not metrics.  The last line is one JSON object naming the device.
With no TPU, the script exits non-zero before running any phase.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

SF10_STORE_SALES_ROWS = 28_800_991      # TPC-DS spec row count at SF10
# the four-chip phase checks placement and equality across devices.  It
# runs at SF1: the sharded programs (per-shard mask under shard_map, the
# count psum, the global compaction) are the same functions at SF10,
# whose only difference is the static capacity (2^25 rows against
# 2^22), and tests/test_tpu_compile.py compiles them at 2^25 for a 2x2
# v5e mesh.  Compaction outputs of 2^22 rows and more compile for ~13 s
# each on the v5e compiler, so SF10 would spend the call compiling.
SF1_STORE_SALES_ROWS = 2_880_404
DEVICE_BUDGET_BYTES = 8 << 30
MAX_BATCH = 8
N_PARTITIONS = 8


def _require_tpu():
    """The platform check that runs before anything else."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found platform "
              f"{devices[0].platform!r}); nothing was run",
              file=sys.stderr)
        sys.exit(2)
    return devices


# ---------------------------------------------------------------------------
# compile accounting (jax.monitoring events)
# ---------------------------------------------------------------------------
class CompileCounter:
    """Backend compiles (persistent-cache hits included) and their
    seconds, from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self) -> tuple:
        return (self.compiles, self.compile_s, self.cache_hits)

    def since(self, mark: tuple) -> dict:
        return {"compiles": self.compiles - mark[0],
                "compile_s": round(self.compile_s - mark[1], 3),
                "cache_hits": self.cache_hits - mark[2]}


# ---------------------------------------------------------------------------
# plain NumPy reference (independent of the engine)
# ---------------------------------------------------------------------------
# the parameters of tpcds_queries(), family by family, in query order
F1 = [(1998, b"Books"), (1999, b"Books"), (2000, b"Electronics"),
      (2001, b"Electronics"), (1998, b"Home"), (1999, b"Sports"),
      (2000, b"Toys"), (2001, b"Music"), (1999, b"Shoes"),
      (2000, b"Books")]
F2_THR = (50, 60, 70, 80, 90, 55, 65, 75)
F2_MIN_QTY = (10, 25)
F3 = [(b"F", 1960), (b"M", 1960), (b"F", 1975), (b"M", 1975),
      (b"F", 1990), (b"M", 1990), (b"F", 1950), (b"M", 1950)]
F4 = [b"CA", b"TX", b"NY", b"WA", b"GA", b"OH", b"IL", b"MI"]
F5 = (0.0, 10.0, 20.0, 30.0, 40.0, 50.0)
F6 = [(1998, 11), (1998, 12), (1999, 11), (1999, 12), (2000, 6),
      (2000, 7), (2001, 1), (2001, 2)]
FAMILY_OF = (["F1"] * 10 + ["F2"] * 10 + ["F3"] * 8 + ["F4"] * 8
             + ["F5"] * 6 + ["F6"] * 8)
# f32 sums: the engine adds f32 values in an order the device chooses,
# the reference adds the same values in f64.  Any f32 summation order
# stays within gamma_(n-1) * sum(|x|) of the exact sum, gamma_k =
# k*u / (1 - k*u) with u = 2**-24 (Higham, "Accuracy and Stability of
# Numerical Algorithms", 2nd ed., Eq. 4.4), so that is the tolerance
# per group of n rows.
F32_UNIT = 2.0 ** -24


def _str_eq(col: np.ndarray, value: bytes) -> np.ndarray:
    pad = np.zeros(col.shape[1], np.uint8)
    pad[: len(value)] = np.frombuffer(value[: col.shape[1]], np.uint8)
    return (col == pad).all(axis=1)


def _group(keys: np.ndarray, sums: dict, counts: bool = False):
    """Group-by over integer keys: (columns, f32 tolerances)."""
    uniq, inv = np.unique(keys, return_inverse=True)
    n = np.bincount(inv, minlength=len(uniq))
    cols, tol = {}, {}
    for name, vals in sums.items():
        if vals.dtype.kind == "f":
            v64 = vals.astype(np.float64)
            cols[name] = np.bincount(inv, weights=v64, minlength=len(uniq))
            gamma = (n - 1) * F32_UNIT / (1 - (n - 1) * F32_UNIT)
            tol[name] = gamma * np.bincount(inv, weights=np.abs(v64),
                                            minlength=len(uniq))
        else:
            cols[name] = np.bincount(
                inv, weights=vals.astype(np.float64),
                minlength=len(uniq)).astype(np.int64)
    if counts:
        cols["n"] = n
    return uniq, cols, tol


def reference(catalog: dict, q: int):
    """Query ``q`` of ``tpcds_queries`` over the host columns: filters
    as boolean masks, joins by lookup on the dense dimension keys.
    Returns (columns in the engine's output order, f32 tolerances)."""
    ss = catalog["store_sales"][2]
    it = catalog["item"][2]
    cu = catalog["customer"][2]
    st = catalog["store"][2]
    dd = catalog["date_dim"][2]
    for dim, key in ((it, "i_item_sk"), (cu, "c_customer_sk"),
                     (st, "s_store_sk"), (dd, "d_date_sk")):
        if not np.array_equal(dim[key], np.arange(len(dim[key]))):
            raise ValueError(f"{key} is not dense: lookup joins need it")
    fam = FAMILY_OF[q]
    if fam == "F1":
        year, cat = F1[q]
        ok = (_str_eq(it["i_category"], cat)[ss["ss_item_sk"]]
              & (dd["d_year"] == year)[ss["ss_sold_date_sk"]])
        keys, cols, tol = _group(
            it["i_brand_id"][ss["ss_item_sk"][ok]],
            {"total_sales": ss["ss_ext_sales_price"][ok]}, counts=True)
        return {"i_brand_id": keys, **cols}, tol
    if fam == "F2":
        j = q - 10
        if j < len(F2_THR):
            ok = ss["ss_sales_price"] > np.float32(F2_THR[j])
            ok &= ss["ss_quantity"] >= 10
        else:
            ok = ss["ss_sales_price"] < ss["ss_wholesale_cost"]
            ok &= ss["ss_quantity"] >= F2_MIN_QTY[j - len(F2_THR)]
        return {n: ss[n][ok] for n in ("ss_item_sk", "ss_customer_sk",
                                        "ss_sales_price",
                                        "ss_net_profit")}, {}
    if fam == "F3":
        gender, y0 = F3[q - 20]
        ok = (_str_eq(cu["c_gender"], gender)
              & (cu["c_birth_year"] >= y0))[ss["ss_customer_sk"]]
        keys, cols, tol = _group(
            cu["c_birth_year"][ss["ss_customer_sk"][ok]],
            {"spend": ss["ss_ext_sales_price"][ok]})
        return {"c_birth_year": keys, **cols}, tol
    if fam == "F4":
        ok = _str_eq(st["s_state"], F4[q - 28])[ss["ss_store_sk"]]
        keys, cols, tol = _group(
            ss["ss_store_sk"][ok],
            {"profit": ss["ss_net_profit"][ok],
             "vol": ss["ss_quantity"][ok]})
        return {"s_store_sk": keys, **cols}, tol
    if fam == "F5":
        ok = ss["ss_net_profit"] > np.float32(F5[q - 36])
        profit = ss["ss_net_profit"][ok]
        top = np.argsort(-profit, kind="stable")[:100]
        return {"ss_item_sk": ss["ss_item_sk"][ok][top],
                "ss_net_profit": profit[top]}, {}
    year, moy = F6[q - 42]
    ok = ((dd["d_year"] == year) & (dd["d_moy"] == moy)
          )[ss["ss_sold_date_sk"]]
    keys, cols, tol = _group(
        it["i_category_id"][ss["ss_item_sk"][ok]],
        {"rev": ss["ss_ext_sales_price"][ok]})
    return {"i_category_id": keys, **cols}, tol


def _row_order(cols: dict) -> np.ndarray:
    return np.lexsort([cols[n] for n in reversed(list(cols))])


def same_multiset(a: dict, b: dict) -> bool:
    """Row-multiset equality of two column dicts (exact)."""
    if list(a) != list(b):
        return False
    if any(len(a[n]) != len(b[n]) for n in a):
        return False
    if all(np.array_equal(a[n], b[n]) for n in a):
        return True                  # same rows in the same order
    oa, ob = _row_order(a), _row_order(b)
    return all(np.array_equal(a[n][oa], b[n][ob]) for n in a)


def check_reference(got: dict, want: dict, tol: dict) -> float:
    """Exact on keys, counts, integer sums and raw rows; f32 sums within
    ``tol``.  Returns the largest f32 error as a share of its bound."""
    if list(got) != list(want):
        raise AssertionError(f"columns {list(got)} != {list(want)}")
    n = {len(v) for v in got.values()} | {len(v) for v in want.values()}
    if len(n) != 1:
        raise AssertionError(f"row counts differ: {n}")
    if not tol:
        if not same_multiset(got, want):
            raise AssertionError("rows differ from the reference")
        return 0.0
    # one row per group: align on the group key (first column)
    order = np.argsort(got[next(iter(got))], kind="stable")
    worst = 0.0
    for name in got:
        g, w = got[name][order], want[name]
        if name in tol:
            err = np.abs(g.astype(np.float64) - w)
            if not (err <= tol[name]).all():
                raise AssertionError(f"{name}: f32 sum outside bound")
            worst = max(worst, float(np.max(err / np.maximum(
                tol[name], np.finfo(np.float64).tiny), initial=0.0)))
        elif not np.array_equal(g.astype(np.int64), w.astype(np.int64)):
            raise AssertionError(f"{name}: differs from the reference")
    return worst


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def _device_bytes_in_use(device):
    stats = device.memory_stats()
    return None if stats is None else stats.get("bytes_in_use")


def load_phase(scale_rows: int, seed: int, budget_bytes: int):
    """Generate the catalog from ``seed`` and register it in a Session
    with the Pallas filter route on; everything else at defaults."""
    from repro.relational import ExecutionConfig, MemoryConfig, \
        SessionConfig
    from repro.relational.tpcds import (build_tpcds_session,
                                        generate_tpcds_catalog)

    config = SessionConfig(
        execution=ExecutionConfig(use_pallas_filter=True),
        memory=MemoryConfig(budget_bytes=budget_bytes))
    sess = build_tpcds_session(scale_rows, seed=seed, config=config)
    # the same seed regenerates the same host columns for the reference
    catalog = generate_tpcds_catalog(scale_rows, seed)
    ss = catalog["store_sales"]
    summary = {"store_sales_rows": ss[1],
               "store_sales_host_bytes": int(sum(
                   a.nbytes for a in ss[2].values())),
               "tables": {n: int(v[1]) for n, v in catalog.items()}}
    return sess, catalog, summary


def fact_resident_bytes(sess) -> int:
    """Device bytes of store_sales columns held in the scan pool."""
    return sum(e.nbytes for k, e in sess._scan_pool.entries.items()
               if k[0] == "store_sales" and e.tier == "device")


def serve_phase(svc, queries, on_first_window=None):
    """Submit every query, resolve every handle; returns the handles."""
    handles = []
    for q in queries:
        handles.append(svc.submit(q))
        if on_first_window is not None and svc.pending == 0:
            on_first_window()        # this submit closed a window
            on_first_window = None
    svc.flush()
    failed = [i for i, h in enumerate(handles) if not h.done or h.failed]
    if failed:
        errs = {i: repr(handles[i].error) for i in failed[:3]}
        raise AssertionError(f"handles failed: {failed} {errs}")
    return handles


def pass_summary(svc, handles, before: dict) -> dict:
    counters = svc.metrics_report()["registry"]["counters"]

    def delta(name):
        return counters.get(name, 0) - before.get(name, 0)

    strict, hits = set(), 0
    for h in handles:
        for ce in h.explain_report().ces:
            strict.add(ce.strict_psi)
            hits += int(ce.cache_hit)
    return {"queries": len(handles), "windows": delta("windows.closed"),
            "ces_used": len(strict), "resident_ce_reads": hits,
            "batched_dispatches": delta("dispatch.batched"),
            "pallas_dispatches": delta("dispatch.pallas"),
            "rows_out": int(sum(h.result().nrows for h in handles))}


def degradation_counters(svc) -> dict:
    """Non-zero degradation/fault counters of the session registry."""
    counters = svc.metrics_report()["registry"]["counters"]
    return {k: v for k, v in counters.items()
            if v and (k.startswith("events.action.")
                      or k.startswith("fault."))}


def tables_of(results) -> list:
    return [t.to_numpy() for t in results]


def check_no_degradation(svc, batches=(), pallas: bool = True) -> None:
    """Fail on any degradation/fault counter, resilience event or pool
    audit violation; with ``pallas``, also unless every slotted mask
    dispatch ran the Pallas kernel."""
    bad = degradation_counters(svc)
    if bad:
        raise AssertionError(f"degradation/fault counters: {bad}")
    for b in batches:
        if b.resilience.get("events"):
            raise AssertionError(f"resilience events: "
                                 f"{b.resilience['events'][:3]}")
        audit = b.resilience.get("audit")
        if audit and audit["violations"]:
            raise AssertionError(f"pool audit: {audit['violations']}")
    if not pallas:
        return
    counters = svc.metrics_report()["registry"]["counters"]
    if counters.get("dispatch.pallas", 0) == 0:
        raise AssertionError("the Pallas kernel route never ran")
    if counters.get("dispatch.xla_slotted", 0):
        raise AssertionError("a kernel-eligible predicate ran on XLA")


def run_single_chip(scale_rows: int, seed: int, budget_bytes: int,
                    device, emit=None) -> dict:
    """Every phase of the one-chip smoke; raises on the first failure.
    Returns the per-phase summaries."""
    emit = emit or PhaseLog()
    from repro.relational import QueryService
    from repro.relational.tpcds import tpcds_queries

    compiles = CompileCounter()

    t0, m0 = time.perf_counter(), compiles.mark()
    sess, catalog, load = load_phase(scale_rows, seed, budget_bytes)
    queries = tpcds_queries(sess)
    emit("load", **load, setup_wall_s=_since(t0), **compiles.since(m0))

    svc = QueryService(sess, max_batch=MAX_BATCH)
    resident = {}

    def first_window():
        resident["fact_bytes"] = fact_resident_bytes(sess)
        resident["bytes_in_use"] = _device_bytes_in_use(device)

    for name in ("serve_cold", "serve_warm"):
        before = svc.metrics_report()["registry"]["counters"]
        t0, m0 = time.perf_counter(), compiles.mark()
        handles = serve_phase(
            svc, queries,
            on_first_window=first_window if name == "serve_cold" else None)
        summary = pass_summary(svc, handles, before)
        if name == "serve_cold":
            if resident["fact_bytes"] <= 0:
                raise AssertionError("no store_sales column is resident")
            in_use = resident["bytes_in_use"]
            if in_use is not None and in_use < resident["fact_bytes"]:
                raise AssertionError(
                    f"bytes_in_use {in_use} < resident fact columns "
                    f"{resident['fact_bytes']}")
            emit("first_window", fact_resident_bytes=resident[
                "fact_bytes"], device_bytes_in_use=in_use)
            mqo_tables = tables_of(h.result() for h in handles)
        else:
            if summary["resident_ce_reads"] == 0:
                raise AssertionError("warm pass read no resident CE")
            if summary["batched_dispatches"] == 0:
                raise AssertionError("warm pass ran no batched dispatch")
            warm_tables = tables_of(h.result() for h in handles)
        emit(name, **summary, setup_wall_s=_since(t0),
             **compiles.since(m0))

    t0, m0 = time.perf_counter(), compiles.mark()
    base = sess.run_batch(queries, mqo=False)
    base_tables = tables_of(r.table for r in base.results)
    diff = [q for q in range(len(queries))
            if not (same_multiset(mqo_tables[q], base_tables[q])
                    and same_multiset(warm_tables[q], base_tables[q]))]
    if diff:
        raise AssertionError(f"MQO results differ from mqo=False: {diff}")
    emit("check_mqo_off", queries=len(queries), equal=True,
         setup_wall_s=_since(t0), **compiles.since(m0))

    t0 = time.perf_counter()
    worst, checked = 0.0, {}
    for q in range(len(queries)):
        want, tol = reference(catalog, q)
        try:
            worst = max(worst, check_reference(mqo_tables[q], want, tol))
        except AssertionError as exc:
            raise AssertionError(f"query {q} ({FAMILY_OF[q]}): {exc}")
        checked[FAMILY_OF[q]] = checked.get(FAMILY_OF[q], 0) + 1
    emit("check_reference", per_family=checked,
         worst_f32_error_over_bound=worst, setup_wall_s=_since(t0))

    check_no_degradation(svc, [base])
    counters = svc.metrics_report()["registry"]["counters"]
    emit("no_degradation", pallas_dispatches=counters.get(
        "dispatch.pallas", 0), device_bytes_in_use=_device_bytes_in_use(
        device))
    return emit.phases


def run_sharded(scale_rows: int, seed: int, budget_bytes: int,
                n_devices: int, emit=None) -> dict:
    """The sharded-scan path: store_sales range-partitioned on
    ss_sold_date_sk and row-sharded over an ``n_devices`` mesh, F2 and
    F5 served sharded (windows without MQO, so every program is a scan)
    and compared with one device in this process.  Returns the
    per-phase summaries."""
    emit = emit or PhaseLog()
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_test_mesh
    from repro.relational import (ExecutionConfig, MemoryConfig,
                                  Partitioning, QueryService, Session,
                                  SessionConfig)
    from repro.relational.datagen import make_storage
    from repro.relational.tpcds import generate_tpcds_catalog, \
        tpcds_queries

    compiles = CompileCounter()
    t0, m0 = time.perf_counter(), compiles.mark()
    catalog = generate_tpcds_catalog(scale_rows, seed)
    nrows = catalog["store_sales"][1]
    mesh = make_test_mesh((n_devices,), ("data",))

    def session(sharding):
        sess = Session.from_config(SessionConfig(
            execution=ExecutionConfig(sharding=sharding),
            memory=MemoryConfig(budget_bytes=budget_bytes)))
        for name, (schema, n, cols) in catalog.items():
            st, _ = make_storage(name, schema, n, "columnar", cols=cols)
            part = (Partitioning("ss_sold_date_sk", "range", N_PARTITIONS)
                    if name == "store_sales" else None)
            sess.register(st, columnar_for_stats=cols, partitioning=part)
        return sess

    sharded = session(NamedSharding(mesh, P("data")))
    single = session(None)
    pick = [q for q, f in enumerate(FAMILY_OF) if f in ("F2", "F5")]
    emit("load_sharded", store_sales_rows=nrows, devices=n_devices,
         setup_wall_s=_since(t0), **compiles.since(m0))

    results = {}
    for name, sess in (("sharded", sharded), ("single", single)):
        t0, m0 = time.perf_counter(), compiles.mark()
        queries = tpcds_queries(sess)
        svc = QueryService(sess, max_batch=MAX_BATCH, mqo=False)
        handles = serve_phase(svc, [queries[q] for q in pick])
        # under a row sharding the masks run in XLA, partitioned per
        # shard; the Pallas kernel is not on this path
        check_no_degradation(svc, pallas=False)
        results[name] = tables_of(h.result() for h in handles)
        emit(f"serve_{name}", queries=len(pick),
             setup_wall_s=_since(t0), **compiles.since(m0))

    spans = {len(e.payload.sharding.device_set)
             for e in sharded._scan_pool.entries.values()
             if isinstance(e.payload, jax.Array)}
    if spans != {n_devices}:
        raise AssertionError(f"scan-pool entries span {spans} devices")
    diff = [q for q, a, b in zip(pick, results["sharded"],
                                 results["single"])
            if not same_multiset(a, b)]
    if diff:
        raise AssertionError(f"sharded results differ: {diff}")
    emit("check_sharded", queries=len(pick), equal=True,
         scan_entries=len(sharded._scan_pool.entries),
         devices_per_entry=n_devices)
    return emit.phases


def _since(t0: float) -> float:
    return time.perf_counter() - t0


class PhaseLog:
    """Per-phase summaries: each printed as one ``phase {...}`` line and
    kept in ``phases``."""

    def __init__(self):
        self.phases = {}

    def __call__(self, phase: str, **fields) -> None:
        self.phases[phase] = fields
        print("phase " + json.dumps({"phase": phase, **fields}),
              flush=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, default=1, choices=(1, 4),
                        help="4 runs only the sharded-scan phase")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    devices = _require_tpu()
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    dev = devices[0]
    stats = dev.memory_stats() or {}
    emit = PhaseLog()
    emit("device", kind=dev.device_kind, count=len(devices),
         bytes_limit=stats.get("bytes_limit"), compile_cache=cache_dir)
    if args.chips == 4:
        if len(devices) < 4:
            print(f"chip_smoke: --chips 4 needs 4 devices, found "
                  f"{len(devices)}", file=sys.stderr)
            return 2
        run_sharded(SF1_STORE_SALES_ROWS, args.seed, DEVICE_BUDGET_BYTES,
                    4, emit=emit)
    else:
        run_single_chip(SF10_STORE_SALES_ROWS, args.seed,
                        DEVICE_BUDGET_BYTES, dev, emit=emit)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
