"""CPU tests of the chip benchmark's yardstick: the reference against the
engine at a tiny size, the traffic generator, the trace reduction on a
recorded trace, the control, and runs of the harness with the timed path
broken underneath, which must come out as not correct.

The harness's functions are called directly; the CLI is only run to see
it refuse a machine without a TPU.
"""
import asyncio
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import (check, control, datagen, harness,  # noqa: E402
                       loadgen, tracereduce)
from chipbench.reference import Reference, bf16_round  # noqa: E402

TINY_ROWS = 6000
HERE = Path(__file__).resolve().parent


def tiny_config(name="tpcds-sf1"):
    """The configuration with every table cut by the same factor;
    date_dim keeps its calendar, and every table keeps at least 8 rows
    (one store per state).  A float32 sum is held to its whole Higham
    bound: the configuration's limit is set at its own size, and small
    groups read a larger share of their bound."""
    config = harness.load_json(harness.HERE / "configs" / f"{name}.json")
    f = TINY_ROWS / config["tables"]["store_sales"]["rows"]
    config["tables"] = {
        t: {**v, "rows": (v["rows"] if t == "date_dim"
                          else max(int(round(v["rows"] * f)), 8))}
        for t, v in config["tables"].items()}
    config["tables"]["store_sales"]["rows"] = TINY_ROWS
    config["limits"]["f32_sum_error_of_bound"] = 1.0
    return config


def datepart(config):
    """The configuration with ``store_sales`` range-partitioned on its
    sale date into 8 partitions."""
    config["tables"]["store_sales"]["partitioning"] = {
        "column": "ss_sold_date_sk", "scheme": "range", "n_partitions": 8}
    return config


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    """The harness turns on JAX's persistent cache; a test process keeps
    it off, so other tests in the same worker are not affected."""
    import repro.launch.compile_cache as cc

    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "off")


# ---------------------------------------------------------------------------
# reference against the engine, family by family
# ---------------------------------------------------------------------------
def _serve(sess, rels):
    from repro.relational import AsyncConfig, AsyncQueryService

    async def go():
        async with AsyncQueryService(sess, config=AsyncConfig(
                max_batch=8)) as svc:
            handles = [await svc.submit(r) for r in rels]
            await svc.flush()
            return [(await h).to_numpy() for h in handles]
    return asyncio.run(go())


@pytest.mark.parametrize("seed", [3, 2**31 + 11, 987654321987])
def test_families_match_reference_through_async_front(seed):
    from repro.relational import c

    config = tiny_config()
    catalog = datagen.generate(config, seed)
    sess = harness.build_session(config, catalog)
    tables = {t: sess.table(t) for t in datagen.COLUMNS}
    spec = loadgen.load("adhoc")
    mix = loadgen.Mix(spec)
    rng = np.random.default_rng(seed)
    queries = []
    for fam in spec["cycle"]:
        pool = mix.pools[fam]
        for j in rng.choice(len(pool), 2, replace=False):
            queries.append((fam, pool[j]))
    families = harness.load_families(spec["cycle"])
    got = _serve(sess, [families[f].build(tables, c, p) for f, p in queries])
    numbers = check.check_answers(
        [(f, p, g) for (f, p), g in zip(queries, got)], Reference(catalog),
        families)
    assert numbers["mismatched_answers"] == 0, numbers
    assert numbers["unanswered"] == 0
    assert numbers["f32_sum_error_of_bound"] <= \
        config["limits"]["f32_sum_error_of_bound"]


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------
def _take(streams, steps):
    return [[next(s) for _ in range(steps)] for s in streams]


@pytest.mark.parametrize("mix", ["adhoc", "dashboard", "dashboard-replay2"])
def test_traffic_is_the_same_every_time(mix):
    spec = loadgen.load(mix)
    a = _take(loadgen.Mix(spec).streams(), 12)
    b = _take(loadgen.Mix(spec).streams(), 12)
    assert a == b
    assert len(a) == spec["clients"]


def test_adhoc_literals_are_fresh_and_cycle_families():
    spec = loadgen.load("adhoc")
    steps = _take(loadgen.Mix(spec).streams(), 6)
    cycle = spec["cycle"]
    for i, client in enumerate(steps):
        assert [g[0][0] for g in client] == [
            cycle[(i + k) % len(cycle)] for k in range(6)]
    seen = [json.dumps(q, sort_keys=True) for client in steps
            for g in client for q in g]
    assert len(seen) == len(set(seen))      # no literal repeats


def test_dashboard_panels_repeat_per_client():
    spec = loadgen.load("dashboard")
    for client in _take(loadgen.Mix(spec).streams(), 3):
        assert client[0] == client[1] == client[2]
        assert [f for f, _ in client[0]] == spec["cycle"]


# ---------------------------------------------------------------------------
# the CLI refuses a machine without a TPU
# ---------------------------------------------------------------------------
def test_cli_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"), "--workload",
         "sf1-dashboard", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------
def test_reduction_on_handmade_trace():
    ev = lambda ts, dur, module="jit_a": {  # noqa: E731
        "plane": "/device:TPU:0", "module": module, "name": "%op",
        "ts": float(ts), "dur": float(dur), "hlo": ""}
    record = {
        "device": [ev(0, 10), ev(5, 10), ev(30, 10, "jit_b"),
                   ev(95, 20, "jit_b")],
        "host": [{"thread": "t", "name": "bench.window", "ts": 0.0,
                  "dur": 100.0},
                 {"thread": "t", "name": "bench.fetch", "ts": 15.0,
                  "dur": 15.0},
                 {"thread": "t", "name": "outer", "ts": 10.0, "dur": 80.0}]}
    red = tracereduce.reduce(record)
    # busy: [0, 15] + [30, 40] + [95, 100] (clipped) = 30 ns
    assert red["busy_s"] == pytest.approx(30e-9)
    assert red["window_s"] == pytest.approx(100e-9)
    assert dict(red["device_ops"]) == pytest.approx(
        {"jit_a": 20e-9, "jit_b": 15e-9})
    # gaps: [15, 30] under bench.fetch (innermost), [40, 95] under outer
    assert dict(red["idle_gaps"]) == pytest.approx(
        {"bench.fetch": 15e-9, "outer": 55e-9})


def test_reduction_on_recorded_trace():
    record = json.loads((HERE / "trace_small.json").read_text())
    red = tracereduce.reduce(record)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["window_s"] == pytest.approx(0.36)
    secs = [s for _, s in red["device_ops"]]
    assert secs == sorted(secs, reverse=True) and len(secs) <= 10
    assert sum(s for _, s in red["idle_gaps"]) <= \
        red["window_s"] - red["busy_s"] + 1e-9
    # the recorded trace holds Pallas mask launches of two queries over a
    # 2^21-row slice: s32[1] rows, two (2, 1) constant tables, an s32 and
    # an f32 column read, a (2, 2^21) mask written at one byte
    from chipbench.metrics import mask_kernel_roofline as roof

    kern = tracereduce.kernel_events(record, roof.PATTERN)
    assert kern, "no mask kernel in the recorded trace"
    rows = 2 ** 21
    want = 4 + 8 + 8 + 4 * rows + 4 * rows + 2 * rows
    assert roof.kernel_bytes(kern[0]["hlo"]) == want
    run = harness.RunRecord(setup_s=1, t_start=0,
                            trace_record=record,
                            peaks=harness.load_json(harness.HERE
                                                    / "peaks.json"),
                            device_kind="TPU v5 lite")
    assert 0 < roof.read(run) < 100


def test_peaks_table_names_its_source():
    peaks = harness.load_json(harness.HERE / "peaks.json")
    assert "TPU v5e" in peaks["source"]
    assert peaks["devices"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


# ---------------------------------------------------------------------------
# the comparison and its control
# ---------------------------------------------------------------------------
def test_sort_limit_ties_accept_any_row_at_the_cut():
    from chipbench.reference import Answer

    want = Answer({"k": np.array([9.0]), "v": np.array([1])},
                  ties=("k", 5.0, {"k": np.array([5.0, 5.0, 5.0]),
                                   "v": np.array([7, 8, 9])}, 1))
    ok = {"k": np.array([9.0, 5.0]), "v": np.array([1, 8])}
    bad = {"k": np.array([9.0, 5.0]), "v": np.array([1, 6])}
    assert check.compare(ok, want)[0]
    assert not check.compare(bad, want)[0]


def test_bf16_rounding_is_to_nearest_even():
    x = np.array([1.0, 1.00390625, 1.01171875, -3.0], np.float32)
    assert bf16_round(x).tolist() == [1.0, 1.0, 1.015625, -3.0]


@pytest.mark.parametrize("cell", ["sf10-adhoc", "sf1-dashboard",
                                  "sf10-dashboard"])
def test_control_is_not_correct(cell):
    bench = harness.load_benchmark()
    w = harness.cell_of(bench, cell)
    config = tiny_config(w["config"])
    numbers = control.control_numbers(config, loadgen.load(w["traffic"]),
                                      5, 16)
    assert not check.verdict(numbers, config["limits"])
    assert numbers["mismatched_answers"] > 0


# ---------------------------------------------------------------------------
# whole runs on the CPU, sound and with the timed path broken
# ---------------------------------------------------------------------------
def _run(cell, hook=None, trace=False, seconds=1.0, config=None):
    import jax

    bench = harness.load_benchmark()
    config = config or tiny_config(harness.cell_of(bench, cell)["config"])
    return harness.run_cell(cell, 2**33 + 5, seconds, trace,
                            t_process=time.monotonic(),
                            devices=jax.devices(), bench=bench,
                            config=config, window_hook=hook)


def test_sound_run_is_correct_and_names_no_device_metric_on_cpu():
    res = _run("sf1-dashboard", trace=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    # no device trace on the CPU: the device metrics stay out of the line
    for name in ("device_ms_per_query", "device_idle_share",
                 "mask_kernel_roofline"):
        assert name not in res["metrics"]
    assert res["metrics"]["window_size_mean"]["value"] > 0
    assert list(res)[-1] == "checks"


def _altered(svc):
    """An answer altered where it is produced: one value of every answer
    moved by one."""
    finish = svc._finish

    def wrong(handle):
        qr = getattr(handle, "_query_result", None)
        if qr is not None and qr.table.nrows:
            t = qr.table
            name = t.schema.names[-1]
            t.columns[name] = t.columns[name].at[0].add(1)
        finish(handle)
    svc._finish = wrong


def _stale(svc):
    """A step that returns its state unchanged: every answer after the
    first of its family is the family's first answer again."""
    finish, first = svc._finish, {}

    def stale(handle):
        qr = getattr(handle, "_query_result", None)
        if qr is not None:
            fam = handle._family
            if fam in first:
                qr.table = first[fam]
            else:
                first[fam] = qr.table
        finish(handle)
    svc._finish = stale


def _half_left_out(svc):
    """Half of each window left out: every second query never runs and
    its handle fails."""
    finish, n = svc._finish, [0]

    def half(handle):
        n[0] += 1
        fut = svc._resolvers.get(handle)
        if n[0] % 2 and fut is not None and not fut.done():
            svc._resolvers.pop(handle)
            svc.admission.release(handle.tenant)
            fut.set_exception(RuntimeError("left out of its window"))
            return
        finish(handle)
    svc._finish = half


FAULTS = {"answer_altered": _altered, "state_unchanged": _stale,
          "half_left_out": _half_left_out}


@pytest.mark.parametrize(
    "fault,layout",
    [pytest.param(f, None, id=name) for name, f in FAULTS.items()]
    + [pytest.param(f, datepart, id=f"{name}-datepart")
       for name, f in FAULTS.items()])
def test_broken_timed_path_is_not_correct(fault, layout):
    config = tiny_config()
    res = _run("sf1-dashboard", hook=fault,
               config=layout(config) if layout else config)
    assert res["correct"] is False, res["checks"]


# ---------------------------------------------------------------------------
# a partitioned table, and the reset between set-up and the window
# ---------------------------------------------------------------------------
def _logged(err: str, tag: str, phase: str) -> dict:
    for line in err.splitlines():
        if line.startswith(tag + " "):
            fields = json.loads(line[len(tag) + 1:])
            if fields.get("phase") == phase:
                return fields
    raise AssertionError(f"no {tag} {phase} line")


@pytest.mark.parametrize("cell", ["sf10-adhoc", "sf1-dashboard"])
def test_partitioned_run_is_correct_and_reset_drops_reuse_state(
        cell, capsys, monkeypatch):
    """``store_sales`` range-partitioned through the configuration: the
    answers are correct, and the reset after the replay leaves no
    covering expression and no partition bitset, with the fact table's
    partitions still on the device.  Ad-hoc traffic has no warm-up
    steps, so its window starts from that state; a dashboard's warm-up
    refresh builds its panels' state again."""
    seen = {}
    reset = harness.reset_reuse_state

    def spy(sess, *args):
        reset(sess, *args)
        seen["parts"] = sess.catalog["store_sales"].partitions
        seen.update(harness.reuse_state(sess))
        seen["scan"] = list(sess.memory.pools["scan"].keys())
    monkeypatch.setattr(harness, "reset_reuse_state", spy)
    res = _run(cell, config=datepart(tiny_config()))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert seen["parts"] is not None and seen["parts"].n_partitions == 8
    # the replay left covering expressions and bitsets ...
    dropped = _logged(capsys.readouterr().err, "setup", "reset")["dropped"]
    assert dropped["ce"] > 0 and dropped["pid"] > 0, dropped
    # ... the reset drops them and keeps the partitions' columns
    assert seen["ce"] == [] and seen["pid"] == []
    assert any(k[0] == "store_sales" and "part" in k for k in seen["scan"])


def test_configuration_without_layout_registers_whole(monkeypatch):
    """A table without ``partitioning`` is registered as before: no
    partitioning is passed and the table holds no partitions."""
    from repro.relational import Session

    calls = []
    register = Session.register

    def spy(self, storage, *args, **kw):
        calls.append((storage.name, kw.get("partitioning")))
        return register(self, storage, *args, **kw)
    monkeypatch.setattr(Session, "register", spy)
    config = tiny_config()
    sess = harness.build_session(config, datagen.generate(config, 1))
    assert calls == [(t, None) for t in datagen.COLUMNS]
    assert all(sess.catalog[t].partitions is None for t in datagen.COLUMNS)
    calls.clear()
    config = datepart(tiny_config())
    sess = harness.build_session(config, datagen.generate(config, 1))
    assert [t for t, p in calls if p is not None] == ["store_sales"]
    assert calls[0][1].column == "ss_sold_date_sk"


# ---------------------------------------------------------------------------
# the window's registry counters and their readers
# ---------------------------------------------------------------------------
def _counted_run(**counters):
    return harness.RunRecord(setup_s=1, t_start=0, records=[
        harness.QueryRecord("F1", {}, 0.0, 1.0, cols={}) for _ in range(4)],
        counters=counters)


def test_counter_readers_on_handmade_runs():
    from chipbench.metrics import host_syncs_per_query, redispatch_share

    run = _counted_run(**{"exec.host_syncs": 14,
                          "exec.deferred_dispatches": 10,
                          "exec.redispatches": 3,
                          "exec.redispatches{op=join}": 2})
    assert host_syncs_per_query.read(run) == pytest.approx(3.5)
    assert redispatch_share.read(run) == pytest.approx(0.3)
    # no deferred dispatch: no share; untraced (no counters): nothing
    assert redispatch_share.read(_counted_run(**{"exec.host_syncs": 2})) \
        is None
    untraced = _counted_run()
    untraced.counters = None
    assert host_syncs_per_query.read(untraced) is None
    assert redispatch_share.read(untraced) is None
    assert harness.counter_growth({"a": 2, "b": 1}, {"a": 5, "b": 1,
                                                     "c": 4}) == \
        {"a": 3, "b": 0, "c": 4}


def test_window_counters_reach_the_readers_on_a_traced_run(monkeypatch):
    """A traced run's ``RunRecord.counters`` is the growth of the
    session's registry counters from the window's start to the end of
    the run, as ``benchmarks/exec_counters.py`` reads it, and the two
    readers report it per answer."""
    runs, kept = [], {}
    read_metrics = harness.read_metrics

    def keep_run(entries, run):
        runs.append(run)
        return read_metrics(entries, run)

    def at_window_start(svc):
        kept["registry"] = reg = svc.session.telemetry().registry
        kept["before"] = reg.snapshot()["counters"]

    monkeypatch.setattr(harness, "read_metrics", keep_run)
    res = _run("sf1-dashboard", hook=at_window_start, trace=True)
    assert res["correct"], res["checks"]
    (run,) = runs
    grown = harness.counter_growth(
        kept["before"], kept["registry"].snapshot()["counters"])
    assert run.counters == grown
    answered = res["attempted"] - res["failed"]
    assert grown["exec.host_syncs"] > 0
    assert grown["exec.deferred_dispatches"] > 0
    metrics = res["metrics"]
    assert metrics["host_syncs_per_query"]["value"] == pytest.approx(
        grown["exec.host_syncs"] / answered)
    assert metrics["redispatch_share"]["value"] == pytest.approx(
        grown["exec.redispatches"] / grown["exec.deferred_dispatches"])
    assert metrics["host_syncs_per_query"]["unit"] == "syncs/query"
