"""The outside of a trace: ``_oplog``'s cut of the program's per-operation
record by ``op`` over a synthetic log and a synthetic trace, its readers on
a program that keeps no log (the parent: every one returns None), on a ring
that has overwritten records, and end to end at toy size on the CPU; and
the ``per_layer`` entries that came with it (each finds its reader by
name, lists cells that exist, and moves a metric those cells report)."""

import os

import pytest

import util
from benchmark import harness, xplane
from benchmark.metrics import _oplog, _spans

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SETUP_METRICS = [
    "setup.ingest_s", "setup.ingest_host_prep_s", "setup.ingest_stream_s",
    "setup.compile_s", "setup.compile_wait_s", "setup.fit_s",
    "setup.predict_s",
]
NEW_METRICS = SETUP_METRICS + ["ingest.idle_s.stream",
                               "score.idle_ms.fetch_copy"]


def record(op, name, wall, children=None, compile_s=0.0, **counts):
    return {"op": op, "name": name, "start": 100.0 + op,
            "end": 100.0 + op + wall, "counts": counts,
            "children": children or {},
            "compile": {"hit": int(compile_s > 0), "miss": 0,
                        "seconds": compile_s}}


# set-up: an ingest whose compile outlived it, a fit, a warm predict; the
# window: three predicts, the first of which has left the ring's trace
# (ops 4-6); the check: one more
LOG = [
    record(1, "dmlc.ingest", 4.0, rows=24, children={
        "dmlc.ingest.host_prep": [1, 0.9, 0.9, 0],
        "dmlc.ingest.stream": [1, 2.5, 2.5, 0],
        "dmlc.ingest.put_wait": [12, 2.2, 1.9, 2_688]}),
    record(1, "dmlc.compile", 7.0, compile_s=7.0, program="kfn"),
    record(2, "dmlc.fit", 8.0, compile_s=0.5, rounds=25, children={
        "dmlc.fit.join_warmup": [1, 3.0, 3.0, 0],
        "dmlc.fit.warm_dispatch": [1, 0.25, 0.25, 0],
        "dmlc.fit.dispatch": [1, 0.01, 0.01, 0],
        "dmlc.compile": [1, 0.5, 0.5, 0]}),
    record(3, "dmlc.predict", 0.05, rows=16),
    record(4, "dmlc.predict", 0.003, rows=16),
    record(5, "dmlc.predict", 0.003, rows=16),
    record(6, "dmlc.predict", 0.003, rows=16),
    record(7, "dmlc.predict", 0.2, rows=99),
]
# the window's trace: a worker still compiling for set-up's ingest (op 1:
# it opens nothing), then the calls of ops 4 to 6
SPANS = [
    ("dmlc.compile", 0.0, 0.4, 1),
    ("dmlc.predict", 0.1, 0.2, 4), ("dmlc.predict.put", 0.1, 0.15, 4),
    ("dmlc.predict", 0.2, 0.3, 5), ("dmlc.predict", 0.3, 0.4, 6),
    ("dmlc.predict.fetch", 0.35, 0.4, 6), ("bench.note", 0.0, 1.0, None),
]


def ctx_of(spans, tmp_path):
    lines = []
    ctx = harness.Ctx(root=str(tmp_path), workload="w", config={}, mix={},
                      seed=0, chips=1, say=lines.append)
    ctx.state["_spans.marks"] = _spans.Marks(list(spans), [[]])
    return ctx, lines


def read(name, ctx):
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "metrics", name + ".py")).read(ctx)


def test_the_log_is_cut_by_op_against_the_trace():
    got = _oplog.split(LOG, SPANS)
    assert [(r["op"], r["name"]) for r in got.setup] == [
        (1, "dmlc.ingest"), (1, "dmlc.compile"), (2, "dmlc.fit"),
        (3, "dmlc.predict")]
    assert [r["op"] for r in got.window] == [4, 5, 6]
    assert [r["op"] for r in got.check] == [7]
    # a trace in which nothing opens an operation cuts nothing
    assert _oplog.split(LOG, SPANS[:1] + SPANS[-1:]) is None
    assert _oplog.split(LOG, []) is None


@pytest.mark.parametrize("name, value", [
    ("setup.ingest_s", 4.0), ("setup.ingest_host_prep_s", 0.9),
    ("setup.ingest_stream_s", 2.5), ("setup.compile_s", 7.5),
    ("setup.compile_wait_s", 3.25), ("setup.fit_s", 8.0),
    ("setup.predict_s", 0.05)])
def test_setup_readers_read_set_ups_records_alone(name, value, tmp_path,
                                                  monkeypatch):
    monkeypatch.setattr(_oplog, "fetch", lambda: (LOG, 0))
    ctx, lines = ctx_of(SPANS, tmp_path)
    assert read(name, ctx) == pytest.approx(value)
    # set-up's records are printed once, one line an operation
    assert read(name, ctx) == pytest.approx(value)
    assert [ln.split()[:4] for ln in lines] == [
        ["[oplog]", "op", "1", "dmlc.ingest"],
        ["[oplog]", "op", "1", "dmlc.compile"],
        ["[oplog]", "op", "2", "dmlc.fit"],
        ["[oplog]", "op", "3", "dmlc.predict"]]
    assert "put_wait 12 x 2.2000 (1.9000)" in lines[0]
    assert "compile hit=1 7.0000 s" in lines[1]


def test_a_set_up_without_the_operation_reads_none(tmp_path, monkeypatch):
    """The ingest cell fits nothing and scores nothing in set-up."""
    monkeypatch.setattr(_oplog, "fetch", lambda: (LOG[:2] + LOG[4:], 0))
    ctx, _lines = ctx_of(SPANS, tmp_path)
    assert read("setup.ingest_s", ctx) == pytest.approx(4.0)
    assert read("setup.compile_s", ctx) == pytest.approx(7.0)
    for name in ("setup.compile_wait_s", "setup.fit_s", "setup.predict_s"):
        assert read(name, ctx) is None


def traced_ctx(spans, tmp_path):
    """A context whose device was busy from 0.1 to 0.2 s of a 1 s window."""
    ctx, lines = ctx_of(spans, tmp_path)
    ctx.summary = xplane.summarize({
        "/device:TPU:0": {xplane.OPS_LINE: [("f.1", 0.1, 0.2)],
                          xplane.MODULES_LINE: [("jit_a(1)", 0.1, 0.2)]},
        "/host:CPU": {"main": [("bench.window", 0.0, 1.0),
                               ("bench.op", 0.1, 0.4)]}})
    return ctx, lines


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_return_none_on_a_program_without_the_marks(
        name, tmp_path, monkeypatch):
    """No ``profiler.op_log`` and no ``dmlc.predict.fetch.copy`` span (the
    parent), no ``dmlc.ingest.stream`` span (a cell that does not ingest
    in its window): nothing to read, nothing said, nothing raised."""
    from dmlc_core_tpu.utils import profiler

    monkeypatch.delattr(profiler, "op_log")
    assert _oplog.fetch() is None
    ctx, lines = traced_ctx(SPANS, tmp_path)
    assert read(name, ctx) is None
    assert lines == []


def test_the_two_idle_readers_read_their_spans(tmp_path):
    """Idle inside the span named, per operation: its wall less the
    device's busy time inside it."""
    spans = SPANS + [("dmlc.ingest.stream", 0.15, 0.65, 8),
                     ("dmlc.predict.fetch.copy", 0.175, 0.2, 6),
                     ("dmlc.predict.fetch.copy", 0.3, 0.305, 5)]
    ctx, _lines = traced_ctx(spans, tmp_path)
    assert read("ingest.idle_s.stream", ctx) == pytest.approx(0.45)
    # the median over the calls, in milliseconds
    assert read("score.idle_ms.fetch_copy", ctx) == pytest.approx(2.5)


def test_an_overwritten_ring_gives_no_reading_and_says_so(tmp_path,
                                                          monkeypatch):
    monkeypatch.setattr(_oplog, "fetch", lambda: (LOG[2:], 2))
    ctx, lines = ctx_of(SPANS, tmp_path)
    assert all(read(name, ctx) is None for name in SETUP_METRICS)
    assert len(lines) == 1 and "overwrote 2 records" in lines[0]


def test_new_entries_find_their_readers_cells_and_metric():
    bench = harness.load_benchmark(ROOT)
    cells = {w["name"] for w in bench["workloads"]}
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-len(NEW_METRICS):] \
        == NEW_METRICS                       # appended, in this order
    for name in NEW_METRICS:
        entry = entries[name]
        path = harness.find_file(ROOT, bench["paths"], "metrics",
                                 name + ".py")
        assert callable(harness.load_module(path).read)
        assert entry["workloads"] and set(entry["workloads"]) <= cells
        (moved,) = [m for m in bench["end_to_end"]
                    if m["name"] == entry["moves"]]
        assert set(entry["workloads"]) <= set(moved.get("workloads", cells))
        assert entry["better"] == "lower"
        if name in SETUP_METRICS:
            assert (entry["source"], entry["unit"], entry["moves"]) == (
                "program_counter", "s", "setup_s")
    # set-up fits in every cell but the ingest cell, scores in one
    fits = cells - {"higgs-24m-d6.ingest"}
    assert set(entries["setup.ingest_s"]["workloads"]) == cells
    assert set(entries["setup.compile_s"]["workloads"]) == cells
    assert set(entries["setup.fit_s"]["workloads"]) == fits
    assert set(entries["setup.compile_wait_s"]["workloads"]) == fits
    assert entries["setup.predict_s"]["workloads"] == ["higgs-24m-d6.score"]


@pytest.mark.parametrize("mix, absent", [
    ("tiny-boost", {"setup.predict_s"}),
    ("tiny-ingest", {"setup.compile_wait_s", "setup.fit_s",
                     "setup.predict_s"}),
    ("tiny-score", set())])
def test_a_traced_run_reports_its_set_up(mix, absent, tmp_path, monkeypatch):
    """End to end on the CPU at toy size: the program's own log, the
    run's own trace, the readers as the harness finds them — the
    program's share of a set-up is under the set-up the run prints."""
    import collections

    from dmlc_core_tpu.utils import profiler

    # this process's earlier operations are not this run's set-up
    monkeypatch.setattr(profiler, "_log", collections.deque(
        maxlen=profiler.OP_LOG_RECORDS))
    monkeypatch.setattr(profiler, "_log_appended", 0)
    bench = harness.load_benchmark(ROOT)
    entries = [dict({k: v for k, v in m.items() if k != "workloads"})
               for m in bench["per_layer"] if m["name"] in SETUP_METRICS]
    root = util.make_root(tmp_path, per_layer=entries)
    # a traced run on the CPU has no device plane: a synthetic one
    planes = {
        "/device:TPU:0": {xplane.OPS_LINE: [("f.1", 1.0, 2.0)],
                          xplane.MODULES_LINE: [("jit_a(1)", 1.0, 2.0)]},
        "/host:CPU": {"main": [("bench.window", 0.0, 4.0),
                               ("bench.op", 0.5, 2.5)]}}
    monkeypatch.setattr(xplane, "load", lambda path: planes)
    lines = []
    out = harness.run_cell(root, "tiny." + mix, 2**31 + 39, 0.3, True,
                           require_chip=False, say=lines.append)
    assert out["correct"] is True, lines
    got = {k: v["value"] for k, v in out["metrics"].items()
           if k in SETUP_METRICS}
    assert set(got) == set(SETUP_METRICS) - absent
    assert all(v >= 0 for v in got.values()) and got["setup.ingest_s"] > 0
    (said,) = [ln for ln in lines if ln.startswith("[bench] set-up ")]
    setup_s = float(said.split()[2])
    assert sum(got.get(k, 0.0) for k in ("setup.ingest_s", "setup.fit_s",
                                         "setup.predict_s")) < setup_s
    assert got["setup.ingest_stream_s"] < got["setup.ingest_s"]
    logged = [ln for ln in lines if ln.startswith("[oplog] ")]
    assert any(" dmlc.ingest " in ln for ln in logged)
