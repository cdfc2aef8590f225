"""One run of one benchmark cell, from set-up to the result line.

``run_cell`` does everything after the platform check of ``run.py``:
it makes the cell's data from the seed, registers it in a ``Session``,
warms up the cell's own shapes and state through the same front the
window drives, measures for ``--seconds`` with a closed loop of clients
over ``AsyncQueryService.submit``, frees the program's state, checks
every answer of the window against the plain reference, and returns the
result line.  With ``trace`` the window runs under the profiler and the
program's span tracer, and the result carries the per-layer metrics.

Everything that belongs to one configuration, traffic mix or metric is
found by name: ``configs/<name>.json``, ``traffic/<name>.json``,
``families/<name>.py`` and ``metrics/<name>.py``.

A configuration file states the deployment and how it was cut to size:

* ``source``: the public definition it follows, down to the scale factor;
  ``tables.<name>.rows`` the rows each table holds.
* ``reduced``: every cut of scale from the source (columns left out, rows
  fewer than the source's), each with its reason.
* ``assumed``: every size or distribution the source leaves open and the
  file sets itself.
* ``tables.<name>.partitioning``: the deployment's physical layout, not a
  cut: ``{"column", "scheme", "n_partitions"}``, as
  ``repro.relational.partition.Partitioning`` takes them.  The table is
  registered horizontally partitioned (rows re-clustered, partition
  statistics, pruning, partition-grained covering expressions and the
  partition-bitset pool).  State where the layout comes from beside the
  file's ``source``.  A table without the key is registered whole.
"""
from __future__ import annotations

import asyncio
import contextlib
import gc
import importlib
import json
import math
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

from chipbench import check, datagen, loadgen, tracereduce
from chipbench.reference import Reference

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
# profiler output, inside the checkout (listed in .gitignore); removed
# once the trace is reduced
TRACE_DIR = ROOT / ".chipbench_trace"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell_of(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def metrics_of(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The cell's metrics: end-to-end ones, or per-layer ones with
    ``trace``; an entry with ``workloads`` counts only for those."""
    entries = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in entries if cell in m.get("workloads", [cell])]


def require_chips(n: int):
    """The TPU devices, or NoChip; a device the peaks table does not
    know is an error too."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found platform {devices[0].platform!r}, "
                     f"not a TPU")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devices)}")
    kind = devices[0].device_kind
    if kind not in load_json(HERE / "peaks.json")["devices"]:
        raise NoChip(f"no peaks for device kind {kind!r} in peaks.json")
    return devices


class CompileCounter:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events; ``staging_s`` is the seconds spent tracing, lowering and
    compiling programs in all, loads from the persistent cache included
    (JAX times a load as a backend compile); ``programs`` names each
    program compiled or loaded, in order."""

    STAGES = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.compiles = 0
        self.compile_s = 0.0
        self.staging_s = 0.0
        self.cache_hits = 0
        self.programs: List[str] = []

        def on_duration(event, duration, fun_name="", **_):
            if event in self.STAGES:
                self.staging_s += duration
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += duration
                self.programs.append(fun_name)

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self) -> tuple:
        return (self.compiles, self.compile_s, self.staging_s,
                self.cache_hits)

    def since(self, mark: tuple) -> dict:
        return {"compiles": self.compiles - mark[0],
                "compile_s": self.compile_s - mark[1],
                "staging_s": self.staging_s - mark[2],
                "cache_hits": self.cache_hits - mark[3]}

    def programs_since(self, mark: tuple) -> List[str]:
        return self.programs[mark[0]:]


class GcPauses:
    """The collector's passes in a stretch of time and the seconds they
    held the host, from ``gc.callbacks``.  Logged beside the window, not
    a metric."""

    def __init__(self):
        self.collections = [0, 0, 0]
        self.seconds = 0.0
        self.longest_s = 0.0
        self._t: Optional[float] = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            dt = time.perf_counter() - self._t
            self.collections[info["generation"]] += 1
            self.seconds += dt
            self.longest_s = max(self.longest_s, dt)
            self._t = None

    def close(self) -> dict:
        gc.callbacks.remove(self._on_gc)
        return {"gc_collections": self.collections, "gc_s": self.seconds,
                "gc_longest_s": self.longest_s}


@dataclass
class QueryRecord:
    family: str
    params: dict
    t_submit: float
    t_done: float = 0.0
    cols: Optional[dict] = None
    error: Optional[str] = None
    window_size: int = 0
    reuse: bool = False


@dataclass
class RunRecord:
    """What the metric readers read (``chipbench/metrics/<name>.py``)."""

    setup_s: float
    t_start: float
    records: List[QueryRecord] = field(default_factory=list)
    window_compiles: int = 0
    plan_spans_s: Optional[float] = None      # canonicalize + mqo
    trace: Optional[dict] = None              # tracereduce.reduce
    trace_record: Optional[dict] = None       # tracereduce.extract
    peaks: Optional[dict] = None
    device_kind: str = ""
    # growth of the session's registry counters over the window (traced
    # runs): name, with its labels, -> count
    counters: Optional[Dict[str, float]] = None

    @property
    def answered(self) -> List[QueryRecord]:
        return [r for r in self.records if r.cols is not None]

    @property
    def t_last(self) -> float:
        return max((r.t_done for r in self.answered), default=self.t_start)


def register(sess, table: str, catalog: datagen.Catalog,
             config: dict) -> None:
    """Register (or re-register) one table of the catalog, columnar, in
    the layout the configuration states for it."""
    from repro.relational import (F32, I32, STR, Partitioning, Schema,
                                  make_storage)

    schema = Schema.of(*[
        (n, {"i32": I32, "f32": F32}.get(k) or STR(datagen.str_width(k)))
        for n, k in datagen.COLUMNS[table]])
    st, _ = make_storage(table, schema, datagen.table_rows(catalog, table),
                         "columnar", cols=catalog[table])
    layout = config["tables"][table].get("partitioning")
    sess.register(st, columnar_for_stats=catalog[table],
                  partitioning=None if layout is None
                  else Partitioning(**layout))


def build_session(config: dict, catalog: datagen.Catalog):
    """A Session over the catalog, configured as the config states."""
    from repro.relational import (ExecutionConfig, MemoryConfig, Session,
                                  SessionConfig)

    s = config["session"]
    sess = Session.from_config(SessionConfig(
        execution=ExecutionConfig(use_pallas_filter=bool(s["pallas_filter"])),
        memory=MemoryConfig(budget_bytes=int(s["device_budget_bytes"]))))
    for table in datagen.COLUMNS:
        register(sess, table, catalog, config)
    return sess


def reuse_state(sess) -> Dict[str, list]:
    """The keys of the session's covering-expression (``ce``) and
    partition-bitset (``pid``) pools: what one query leaves for another
    to reuse."""
    return {name: list(pool.keys()) for name, pool in
            sess.memory.pools.items() if name in ("ce", "pid")}


def reset_reuse_state(sess, catalog: datagen.Catalog, config: dict) -> None:
    """Drop what earlier queries left for later ones, keeping every
    table's columns on the device.  Re-registering ``date_dim`` drops
    every covering expression and resident, partition-grained ones too,
    and ``date_dim``'s own partition bitsets (``Session.register``); the
    bitsets that scans of the other tables recorded go with the pid
    pool's entries.

    This is what keeps set-up's promise that the window compiles
    nothing: the replay, the rehearsal and the window each start from
    the state this leaves, so a step of the schedule plans the same
    covering expressions, and runs the same programs, in each of them.
    What the reset keeps (the tables' device columns, whole or per
    partition, and the compiled programs) holds no query's answer."""
    from repro.core.memory import PidPool

    register(sess, "date_dim", catalog, config)
    pid = sess.memory.pools.get(PidPool.POOL)
    if pid is not None:
        pid.clear()


def counter_growth(before: dict, after: dict) -> Dict[str, float]:
    """Each registry counter's growth from one snapshot to a later one."""
    return {k: v - before.get(k, 0) for k, v in after.items()}


def load_families(names) -> Dict[str, object]:
    return {f: importlib.import_module(f"chipbench.families.{f}")
            for f in names}


async def closed_loop(svc, build, streams, records: List[QueryRecord], *,
                      clock, steps: Optional[int] = None,
                      deadline: Optional[Callable[[], float]] = None,
                      annotate=None, explain: bool = False) -> None:
    """Clients over ``svc``: each submits a step's queries, awaits each
    answer and brings its columns to the host, then takes the next step.
    A client stops after ``steps`` steps, or once ``deadline()`` has
    passed and the open window is empty; the queries in flight finish.
    Returns the steps each client took."""
    annotate = annotate or (lambda name: contextlib.nullcontext())
    stop = asyncio.Event()

    async def client(stream):
        n = 0
        # once the deadline has passed a client stops, but only with the
        # open window empty: until then it keeps filling it, so the
        # window ends on whole MQO windows, as the replay does.  The
        # answers of a window wake their clients in one pass of the event
        # loop, before the timer's.
        while steps is None or n < steps:
            if stop.is_set() and svc.pending == 0:
                break
            group = next(stream)
            n += 1
            rels = [build(f, p) for f, p in group]
            # submit never suspends: a step is submitted whole
            with annotate("bench.submit"):
                t0 = clock()
                handles = [await svc.submit(r) for r in rels]
            for (f, p), h in zip(group, handles):
                rec = QueryRecord(f, p, t0)
                try:
                    with annotate("bench.await"):
                        table = await h
                    with annotate("bench.fetch"):
                        rec.cols = table.to_numpy()
                except Exception as exc:   # counted as failed; loop goes on
                    rec.error = repr(exc)
                rec.t_done = clock()
                if explain and rec.error is None:
                    rep = h.explain_report()
                    rec.window_size = rep.window_size
                    rec.reuse = rep.resident_reuse or rep.subsumption_hit
                records.append(rec)
        # a client that stops closes the open window, so nobody waits on
        # a window the stopped clients would have filled
        await svc.flush()
        return n

    async def timer():
        while (left := deadline() - clock()) > 0:
            await asyncio.sleep(left)
        stop.set()

    tasks = [asyncio.create_task(client(s)) for s in streams]
    timer_task = (asyncio.create_task(timer()) if deadline is not None
                  else None)
    try:
        served = await asyncio.gather(*tasks)
    finally:
        if timer_task is not None:
            timer_task.cancel()
            await asyncio.gather(timer_task, return_exceptions=True)
        await svc.flush()
        await svc.drain()
    return list(served)


def _plan_span_seconds(sess, t0: float, t1: float) -> Optional[float]:
    """Seconds in the program's ``canonicalize`` and ``mqo`` spans of the
    windows that started inside [t0, t1]; None when tracing was off."""
    tracer = sess.telemetry().tracer
    if not getattr(tracer, "enabled", False) or not hasattr(tracer,
                                                            "finished"):
        return None
    total = 0.0
    for root in tracer.finished:
        if root.t_start is None or not (t0 <= root.t_start <= t1):
            continue
        for _, sp in root.walk():
            if sp.name in ("canonicalize", "mqo") and sp.duration:
                total += sp.duration
    return total


def _profiler_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # no per-call Python events
    return opts


def read_metrics(entries: List[dict], run: RunRecord) -> Dict[str, dict]:
    """Each metric's reader; a reader that finds nothing returns None
    and the metric is left out of the line."""
    out = {}
    for m in entries:
        reader = importlib.import_module(f"chipbench.metrics.{m['name']}")
        v = reader.read(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def log(tag: str, **fields) -> None:
    print(f"{tag} " + json.dumps(fields, default=str), file=sys.stderr,
          flush=True)


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             t_process: float, devices, bench: Optional[dict] = None,
             config: Optional[dict] = None,
             window_hook: Optional[Callable] = None) -> dict:
    """The whole run after the platform check; returns the result line.

    ``config`` replaces the cell's configuration file (tests run a cut
    copy); ``window_hook(svc)`` is called once set-up is done, before
    the window (tests plant faults in the program there)."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    from repro.relational import AsyncConfig, AsyncQueryService, c

    bench = bench or load_benchmark()
    cell = cell_of(bench, cell_name)
    config = config or load_json(HERE / "configs" / f"{cell['config']}.json")
    spec = loadgen.load(cell["traffic"])
    mix = loadgen.Mix(spec)
    families = load_families(spec["cycle"])
    clock = time.monotonic

    cache_dir = enable_compile_cache()
    compiles = CompileCounter()
    log("setup", phase="init", seconds=clock() - t_process,
        compile_cache=cache_dir)

    t = clock()
    catalog = datagen.generate(config, seed)
    log("setup", phase="datagen", seconds=clock() - t,
        rows={k: datagen.table_rows(catalog, k) for k in catalog})

    t = clock()
    sess = build_session(config, catalog)
    tables = {name: sess.table(name) for name in datagen.COLUMNS}
    log("setup", phase="register", seconds=clock() - t)

    def build(fam, params):
        return families[fam].build(tables, c, params)

    s = config["session"]
    n_batch = int(s["max_batch"])
    if (spec["clients"] * mix.queries_per_step()) % n_batch:
        raise ValueError("clients x queries per step must fill whole "
                         "windows, or a closed loop can wait forever")
    warm_steps = int(spec.get("warmup_steps", 0))
    window_records: List[QueryRecord] = []
    summary: dict = {}

    def service():
        return AsyncQueryService(sess, config=AsyncConfig(
            max_batch=n_batch))

    async def loop(svc, streams, records, **kw) -> int:
        """``closed_loop`` for set-up: a failed query is an error.
        Returns the most steps a client took."""
        served = await closed_loop(svc, build, streams, records,
                                   clock=clock, **kw)
        failed = [r.error for r in records if r.error]
        if failed:
            raise RuntimeError(f"set-up queries failed: {failed[:3]}")
        return max(served, default=0)

    def reset(phase: str) -> None:
        t = clock()
        left = {k: len(v) for k, v in reuse_state(sess).items()}
        reset_reuse_state(sess, catalog, config)
        log("setup", phase=phase, seconds=clock() - t, dropped=left)

    async def replay() -> dict:
        """The window's own schedule, on its own service, from the
        state the window starts from: it compiles every program of the
        steps it serves.  A periodic schedule (dashboards) runs
        ``replay_steps`` steps.  An ad-hoc one runs as long as the window
        will, plus the seconds spent tracing, compiling or loading
        programs, and stops on whole MQO windows, as the window does;
        it cannot see the other costs of a program's first run, so a
        window can get further than it did (``rehearse``)."""
        svc = service()
        done: List[QueryRecord] = []
        streams = mix.streams()
        try:
            await loop(svc, streams, done, steps=warm_steps)
            if "replay_steps" in spec:
                steps = await loop(svc, streams, done,
                                   steps=int(spec["replay_steps"]))
            else:
                end, c0 = clock() + seconds, compiles.staging_s
                steps = await loop(
                    svc, streams, done,
                    deadline=lambda: end + compiles.staging_s - c0)
        finally:
            await svc.aclose()
        return {"queries": len(done), "steps": warm_steps + steps}

    async def rehearse(covered: int) -> dict:
        """The window once more, after the replay and a reset, so that
        no step the window serves is a first run.  It runs by time at
        the window's own pace over the ``covered`` steps the replay
        compiled, and stops on whole MQO windows.  The pace of those
        compiled steps says how many the window will serve; the
        rehearsal then goes on to that many and ``rehearse_margin`` more,
        compiling any step not yet covered.  Where the compiled steps
        were under half of that, their pace says too little: it resets
        and runs again, at most three times in all."""
        per_step = spec["clients"] * mix.queries_per_step()
        margin = float(spec["rehearse_margin"])
        passes = []
        while True:
            svc = service()
            done: List[QueryRecord] = []
            streams = mix.streams()
            try:
                await loop(svc, streams, done, steps=warm_steps)
                gc.collect()        # the replay's garbage, not the pace's
                t0, first = clock(), len(done)
                reach = await loop(svc, streams, done,
                                   deadline=lambda: t0 + seconds)
                # the compiled steps, and when they were done
                warm = min(reach, covered)
                t_warm = sorted(r.t_done for r in done[first:])[
                    warm * per_step - 1] - t0
                pace = max(reach, warm * seconds / t_warm)
                target = math.ceil(pace * (1 + margin))
                if target > reach:
                    await loop(svc, streams, done, steps=target - reach)
            finally:
                await svc.aclose()
            covered = max(covered, target)
            passes.append({"reach": warm_steps + reach, "warm": warm,
                           "warm_s": t_warm, "steps": warm_steps + target})
            if 2 * warm >= pace or len(passes) == 3:
                return {"steps": warm_steps + covered, "passes": passes}
            reset("reset")

    async def serve():
        t, m = clock(), compiles.mark()
        replayed = await replay()
        log("setup", phase="replay", seconds=clock() - t, **replayed,
            **compiles.since(m))
        # the window starts from the state the replay started from, with
        # every program compiled and the tables' columns on the device
        reset("reset")
        if "rehearse_margin" in spec:
            t, r = clock(), compiles.mark()
            rehearsed = await rehearse(replayed["steps"] - warm_steps)
            log("setup", phase="rehearsal", seconds=clock() - t,
                **rehearsed, **compiles.since(r))
            reset("reset")
        t = clock()
        svc = service()
        streams = mix.streams()
        try:
            await loop(svc, streams, [], steps=warm_steps)
            log("setup", phase="warm_state", seconds=clock() - t,
                steps=warm_steps, **compiles.since(m))
            if window_hook is not None:
                window_hook(svc)
            if trace:
                sess.enable_tracing()
                shutil.rmtree(TRACE_DIR, ignore_errors=True)
                jax.profiler.start_trace(str(TRACE_DIR),
                                         profiler_options=_profiler_options())
            registry = sess.telemetry().registry
            before = registry.snapshot()["counters"] if trace else None
            mark = compiles.mark()
            pauses = GcPauses()
            summary["t_start"] = t0 = clock()
            annotate = jax.profiler.TraceAnnotation if trace else None
            try:
                with (annotate("bench.window") if trace
                      else contextlib.nullcontext()):
                    served = await closed_loop(
                        svc, build, streams, window_records, clock=clock,
                        deadline=lambda: t0 + seconds, annotate=annotate,
                        explain=trace)
                if trace:
                    summary["counters"] = counter_growth(
                        before, registry.snapshot()["counters"])
            finally:
                if trace:
                    jax.profiler.stop_trace()
            summary["window"] = compiles.since(mark)
            summary["window"]["steps"] = warm_steps + max(served, default=0)
            summary["window"]["programs"] = compiles.programs_since(mark)
            summary["host"] = pauses.close()
            summary["plan_s"] = _plan_span_seconds(sess, t0, clock())
        finally:
            await svc.aclose()

    asyncio.run(serve())
    run = RunRecord(setup_s=summary["t_start"] - t_process,
                    t_start=summary["t_start"], records=window_records,
                    window_compiles=summary["window"]["compiles"],
                    plan_spans_s=summary["plan_s"],
                    peaks=load_json(HERE / "peaks.json"),
                    device_kind=devices[0].device_kind,
                    counters=summary.get("counters"))
    stats = devices[0].memory_stats() or {}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:int(cell["chips"])])
    if trace:
        run.trace_record = tracereduce.extract(
            tracereduce.find_xplane(str(TRACE_DIR)))
        run.trace = tracereduce.reduce(run.trace_record)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    log("window", queries=len(window_records),
        answered=len(run.answered), **summary["window"],
        bytes_limit=stats.get("bytes_limit"))
    log("host", seconds=run.t_last - run.t_start, **summary["host"])

    # free the program's state before the reference runs
    del sess, tables
    gc.collect()
    metrics = read_metrics(metrics_of(bench, cell_name, trace), run)

    t = clock()
    numbers = check.check_answers(
        [(r.family, r.params, r.cols) for r in window_records],
        Reference(catalog), families)
    log("check", seconds=clock() - t, answers=len(window_records))
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": int(cell["chips"]), "memory_peak_bytes": int(peak)}
    limits = config["limits"]
    result = {"correct": check.verdict(numbers, limits),
              "attempted": len(window_records),
              "failed": sum(r.cols is None for r in window_records),
              "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {
            "device_ops": [list(kv) for kv in run.trace["device_ops"]],
            "idle_gaps": [list(kv) for kv in run.trace["idle_gaps"]]}
    result["checks"] = check.report(numbers, limits)
    return result
