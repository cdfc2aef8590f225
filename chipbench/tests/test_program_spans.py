"""CPU tests of the program's spans as the benchmark reads them: the spans
appear as ``repro.*`` annotations in a profiler trace, and the readers of
``queue_wait_ms`` and ``window_idle_ms_per_query`` are right on handmade
runs, on a whole traced run on the CPU and on a recorded chip trace.
"""
import asyncio
import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness, tracereduce  # noqa: E402
from chipbench.metrics import (queue_wait_ms,  # noqa: E402
                               window_idle_ms_per_query)

HERE = Path(__file__).resolve().parent


def _answers(n):
    return [harness.QueryRecord("F1", {}, 0.0, 1.0, cols={})
            for _ in range(n)]


# ---------------------------------------------------------------------------
# the program's spans in a profiler trace
# ---------------------------------------------------------------------------
def _tiny_session():
    from repro.relational import (I32, MemoryConfig, Schema, Session,
                                  SessionConfig, make_storage)

    schema = Schema.of(("a", I32), ("b", I32), ("c", I32))
    rng = np.random.default_rng(7)
    cols = {k: rng.integers(0, 100, 2000).astype(np.int32) for k in "abc"}
    sess = Session.from_config(SessionConfig(
        memory=MemoryConfig(budget_bytes=1 << 24)))
    st, _ = make_storage("t", schema, 2000, "columnar", cols=cols)
    sess.register(st)
    return sess


def _window(sess):
    """One window of three queries through the async front."""
    from repro.relational import AsyncConfig, AsyncQueryService, c

    t = sess.table("t")
    rels = [t.where(c.a > 50).select("a", "b"),
            t.where(c.b < 20).group_by("c").agg(("n", "count", "")),
            t.where(c.c >= 5).select("a", "c")]

    async def go():
        async with AsyncQueryService(sess, config=AsyncConfig(
                max_batch=3)) as svc:
            handles = [await svc.submit(r) for r in rels]
            await svc.flush()
            return [(await h).to_numpy() for h in handles]
    return asyncio.run(go())


def test_spans_are_annotations_inside_their_window(tmp_path):
    import jax

    sess = _tiny_session()
    _window(sess)                       # compile outside the trace
    tracer = sess.enable_tracing()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _window(sess)
    finally:
        jax.profiler.stop_trace()
    record = tracereduce.extract(tracereduce.find_xplane(str(tmp_path)),
                                 min_host_ns=0)
    host = record["host"]
    windows = [(e["ts"], e["ts"] + e["dur"]) for e in host
               if e["name"] == "repro.window"]
    assert len(windows) == 1
    for name in ("repro.canonicalize", "repro.mqo", "repro.exec.sync"):
        events = [e for e in host if e["name"] == name]
        assert events, f"no {name} in the trace"
        for e in events:
            assert any(a <= e["ts"] and e["ts"] + e["dur"] <= b
                       for a, b in windows), f"{name} outside its window"
    # every span the tracer kept has its annotation, on the same thread
    # as the window's (the async front's executor thread)
    spans = {sp.name for root in tracer.finished for _, sp in root.walk()}
    assert {f"repro.{n}" for n in spans} <= {e["name"] for e in host}
    threads = {e["thread"] for e in host if e["name"].startswith("repro.")}
    assert len(threads) == 1


# ---------------------------------------------------------------------------
# the readers on handmade runs
# ---------------------------------------------------------------------------
def _host(name, ts, dur):
    return {"thread": "t", "name": name, "ts": float(ts), "dur": float(dur)}


def _queries(*pairs):
    """Answered queries of (submit time, window size), answered in the
    reverse of their submit order."""
    return [harness.QueryRecord("F1", {}, t, 1000.0 - t, cols={},
                                window_size=n) for t, n in pairs]


def test_queue_wait_ms_fills_windows_in_submit_order():
    # bench.window opens at 5 s on the trace's clock and at 100 s on the
    # harness's; program windows start 1 s and 3 s into it
    record = {"device": [], "host": [
        _host("bench.window", 5e9, 10e9),
        _host("repro.window", 6e9, 1e9),
        _host("repro.exec.sync", 6.5e9, 1e6),
        _host("repro.window", 8e9, 1e9),
        _host("repro.window", 20e9, 1e9)]}     # outside: not read
    # submitted out of order: the first two (by submit time) run in the
    # window at 101 s, the next three in the one at 103 s
    records = _queries((100.5, 3), (100.0, 2), (100.5, 3), (100.2, 2),
                       (100.5, 3))
    run = harness.RunRecord(setup_s=1, t_start=100.0, records=records,
                            trace_record=record)
    waits = [1.0, 0.8, 2.5, 2.5, 2.5]
    assert queue_wait_ms.read(run) == pytest.approx(
        sum(waits) * 1e3 / 5)
    assert queue_wait_ms.window_starts(record) == pytest.approx([1.0, 3.0])


def test_queue_wait_ms_reads_nothing_where_queries_and_windows_differ():
    record = {"device": [], "host": [
        _host("bench.window", 0, 10e9), _host("repro.window", 1e9, 1e9),
        _host("repro.window", 3e9, 1e9)]}

    def read(*pairs, rec=record):
        return queue_wait_ms.read(harness.RunRecord(
            setup_s=1, t_start=0.0, records=_queries(*pairs),
            trace_record=rec))
    assert read((0.1, 2), (0.2, 2), (0.3, 1)) == pytest.approx(
        (0.9 + 0.8 + 2.7) * 1e3 / 3)
    assert read((0.1, 2), (0.2, 2)) is None                  # too few
    assert read((0.1, 2), (0.2, 2), (0.3, 1), (0.4, 1)) is None  # too many
    assert read((0.1, 2), (0.2, 1), (0.3, 1)) is None        # sizes differ
    assert read((0.1, 0), (0.2, 2), (0.3, 1)) is None        # failed
    # a program without the annotation (the parent), or an untraced run
    bare = {"device": [], "host": [_host("bench.window", 0, 10e9)]}
    assert read((0.1, 2), (0.2, 2), rec=bare) is None
    assert queue_wait_ms.read(harness.RunRecord(
        setup_s=1, t_start=0.0, records=_queries((0.1, 2)))) is None


def test_queue_wait_ms_agrees_with_the_program_on_a_traced_cpu_run(
        monkeypatch):
    """A whole traced run of the cut ``sf1-dashboard`` cell on the CPU:
    the reader's wait per query is the program's own (the ``window``
    spans' ``wait_s``), plus at most the time a client's step takes to
    submit (the reader counts from the step's submit, as the latency
    metrics do; the program from each query's own)."""
    import jax

    from test_chipbench import tiny_config

    runs, kept = [], {}
    read_metrics = harness.read_metrics

    def keep_run(entries, run):
        runs.append(run)
        return read_metrics(entries, run)
    monkeypatch.setattr(harness, "read_metrics", keep_run)
    bench = harness.load_benchmark()
    res = harness.run_cell(
        "sf1-dashboard", 2**33 + 11, 1.0, True, t_process=time.monotonic(),
        devices=jax.devices(), bench=bench,
        config=tiny_config(harness.cell_of(bench, "sf1-dashboard")["config"]),
        window_hook=lambda svc: kept.setdefault("svc", svc))
    assert res["correct"], res["checks"]
    (run,) = runs
    windows = [root.attrs for root in
               kept["svc"].session.telemetry().tracer.finished
               if root.name == "window" and root.t_start >= run.t_start]
    assert sum(a["n_queries"] for a in windows) == len(run.records)
    program_ms = (sum(a["wait_s"] for a in windows) * 1e3
                  / len(run.records))
    submit_ms = max(e["dur"] for e in run.trace_record["host"]
                    if e["name"] == "bench.submit") * 1e-6
    reader_ms = res["metrics"]["queue_wait_ms"]["value"]
    assert program_ms - 0.5 <= reader_ms <= program_ms + submit_ms + 0.5


def _dev(ts, dur, plane="/device:TPU:0"):
    return {"plane": plane, "module": "jit_a", "name": "%op",
            "ts": float(ts), "dur": float(dur), "hlo": ""}


def test_window_idle_splits_idle_by_program_windows():
    record = {
        "device": [_dev(0, 20), _dev(50, 20)],
        "host": [_host("bench.window", 0, 100),
                 _host("repro.window", 10, 30),
                 _host("repro.window", 60, 30),
                 _host("repro.exec.sync", 65, 10)]}
    # windows [10, 40] + [60, 90]: 60 ns, 20 of them busy -> 40 idle;
    # outside them 40 ns, 20 busy -> 20 idle; 60 idle in all
    inside, outside = window_idle_ms_per_query.split_idle(record)
    assert (inside, outside) == (pytest.approx(40.0), pytest.approx(20.0))
    run = harness.RunRecord(setup_s=1, t_start=0, records=_answers(2),
                            trace_record=record)
    assert window_idle_ms_per_query.read(run) == pytest.approx(40e-6 / 2)


def test_window_idle_averages_planes_and_clips_to_the_window():
    record = {
        "device": [_dev(-50, 60), _dev(0, 100, plane="/device:TPU:1"),
                   _dev(500, 10)],
        "host": [_host("bench.window", 0, 100),
                 _host("repro.window", -20, 50),
                 _host("repro.window", 80, 40)]}
    # windows clipped: [0, 30] + [80, 100] = 50 ns.  TPU:0 busy [0, 10]:
    # 40 idle inside, 50 outside; TPU:1 busy throughout: none
    inside, outside = window_idle_ms_per_query.split_idle(record)
    assert (inside, outside) == (pytest.approx(20.0), pytest.approx(25.0))
    red = tracereduce.reduce(record)
    assert inside + outside == pytest.approx(
        (red["window_s"] - red["busy_s"]) * 1e9)


def test_window_idle_finds_nothing_without_program_windows():
    record = {"device": [_dev(0, 20)],
              "host": [_host("bench.window", 0, 100)]}
    assert window_idle_ms_per_query.split_idle(record) is None
    run = harness.RunRecord(setup_s=1, t_start=0, records=_answers(2),
                            trace_record=record)
    assert window_idle_ms_per_query.read(run) is None
    run.trace_record = None
    assert window_idle_ms_per_query.read(run) is None


def test_new_metrics_are_appended_to_the_benchmark():
    bench = harness.load_benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-2:] == ["queue_wait_ms", "window_idle_ms_per_query"]
    for m in bench["per_layer"][-2:]:
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").exists()
        assert m["workloads"] == [w["name"] for w in bench["workloads"]]


# ---------------------------------------------------------------------------
# a recorded chip trace: one sf1-dashboard program window
# ---------------------------------------------------------------------------
def test_window_idle_on_recorded_chip_window():
    """``trace_spans_small.json``: the extracted record of a traced
    ``sf1-dashboard`` run on one TPU v5 lite, cut to one program window
    and the gap before it, with ``bench.window`` set to that cut."""
    record = json.loads((HERE / "trace_spans_small.json").read_text())
    red = tracereduce.reduce(record)
    assert 0 < red["busy_s"] < red["window_s"]
    # the program's phases name the device's idle gaps
    assert any(name.startswith("repro.") for name, _ in red["idle_gaps"])
    inside, outside = window_idle_ms_per_query.split_idle(record)
    assert inside > 0 and outside >= 0
    idle_ns = (1 - red["busy_s"] / red["window_s"]) * red["window_s"] * 1e9
    assert abs(inside + outside - idle_ns) < 1e6          # within 1 ms
    run = harness.RunRecord(setup_s=1, t_start=0, records=_answers(8),
                            trace_record=record)
    assert window_idle_ms_per_query.read(run) == pytest.approx(
        inside * 1e-6 / 8)
