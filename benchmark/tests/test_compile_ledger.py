"""The compile ledger's readers (``metrics/_compile_ledger.py`` and the four
``setup.compile_*`` files): their sums and their ``[compile]`` lines over a
synthetic log, ``None`` on a program whose records have no ``programs`` (the
parent), their ``per_layer`` entries, and end to end at toy size on the
CPU."""

import collections
import os

import pytest

import util
from benchmark import harness, xplane
from benchmark.metrics import _compile_ledger, _oplog, _spans

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

METRICS = ["setup.compile_inline_s", "setup.compile_trace_lower_s",
           "setup.compile_cache_read_s", "setup.compile_key_or_xla_s"]


def entry(program, thread, under, verdict, trace_s, lower_s, backend_s,
          read_s=0.0, **more):
    return {"program": program, "trace_s": trace_s, "lower_s": lower_s,
            "backend_s": backend_s, "read_s": read_s, "verdict": verdict,
            "thread": thread, "under": under, **more}


def record(op, name, programs=None):
    rec = {"op": op, "name": name, "start": 100.0 + op, "end": 101.0 + op,
           "counts": {}, "children": {},
           "compile": {"hit": 0, "miss": 0, "seconds": 0.0}}
    if programs is not None:
        rec["programs"] = programs
    return rec


# set-up: an ingest that read two programs back inline while its worker
# compiled a third, the worker's own record (it outlived the ingest), a fit
# that compiled nothing; the window's op 4 and the check's op 5 read
# programs too, which are not set-up's
PROGRAMS = {
    1: [entry("jit(local_summary)", "own", "dmlc.ingest.cuts", "hit",
              0.125, 0.25, 1.0, read_s=0.75),
        entry("jit(apply_bins)", "own", "dmlc.ingest.bin_dispatch", "miss",
              0.5, 0.25, 8.0)],
    2: [entry("jit(k_rounds_body)", "joined", "dmlc.compile", "hit",
              2.0, 1.0, 4.0, read_s=3.5),
        entry("(more)", "mixed", "mixed", "hit", 0.0, 0.0, 0.5,
              read_s=0.25, n=3)],
}
LOG = [record(1, "dmlc.ingest", PROGRAMS[1]),
       record(1, "dmlc.compile", PROGRAMS[2]),
       record(2, "dmlc.fit", []),
       record(4, "dmlc.fit", [entry("jit(late)", "own", "dmlc.fit", "miss",
                                    64.0, 64.0, 64.0)]),
       record(5, "dmlc.predict", [entry("jit(check)", "own", "dmlc.predict",
                                        "miss", 64.0, 64.0, 64.0)])]
SPANS = [("dmlc.fit", 0.0, 0.5, 4), ("dmlc.fit.dispatch", 0.1, 0.2, 4)]
VALUES = {"setup.compile_inline_s": 0.125 + 0.25 + 1.0 + 0.5 + 0.25 + 8.0,
          "setup.compile_trace_lower_s": 0.375 + 0.75 + 3.0,
          "setup.compile_cache_read_s": 0.75 + 3.5 + 0.25,
          "setup.compile_key_or_xla_s": 0.25 + 8.0 + 0.5 + 0.25}


def ctx_of(tmp_path):
    lines = []
    ctx = harness.Ctx(root=str(tmp_path), workload="w", config={}, mix={},
                      seed=0, chips=1, say=lines.append)
    ctx.state["_spans.marks"] = _spans.Marks(list(SPANS), [[]])
    return ctx, lines


def read(name, ctx):
    return harness.load_module(os.path.join(
        ROOT, "benchmark", "metrics", name + ".py")).read(ctx)


@pytest.mark.parametrize("name", METRICS)
def test_a_reader_sums_set_ups_programs_alone(name, tmp_path, monkeypatch):
    monkeypatch.setattr(_oplog, "fetch", lambda: (LOG, 0))
    ctx, lines = ctx_of(tmp_path)
    assert read(name, ctx) == pytest.approx(VALUES[name])
    assert read(name, ctx) == pytest.approx(VALUES[name])
    # one line a program of set-up, once, after the records' own lines
    said = [ln for ln in lines if ln.startswith("[compile] ")]
    assert len(said) == 4 and lines[-4:] == said
    assert said[0] == (
        "[compile] op 1 dmlc.ingest under dmlc.ingest.cuts own "
        "jit(local_summary) hit trace 0.125 lower 0.250 read 0.750 "
        "rest 0.250")
    assert said[1].split()[3:9] == [
        "dmlc.ingest", "under", "dmlc.ingest.bin_dispatch", "own",
        "jit(apply_bins)", "miss"]
    assert said[2].startswith(
        "[compile] op 1 dmlc.compile under dmlc.compile joined "
        "jit(k_rounds_body) hit ")
    assert " (more) x3 hit " in said[3]


@pytest.mark.parametrize("name", METRICS)
def test_a_set_up_that_compiled_nothing_reads_zero(name, tmp_path,
                                                   monkeypatch):
    monkeypatch.setattr(_oplog, "fetch", lambda: (LOG[2:], 0))
    ctx, lines = ctx_of(tmp_path)
    assert read(name, ctx) == 0.0
    assert not [ln for ln in lines if ln.startswith("[compile] ")]


@pytest.mark.parametrize("name", METRICS)
@pytest.mark.parametrize("log", ["no_programs", "no_log", "overwritten"])
def test_a_reader_returns_none_where_there_is_nothing_to_read(
        name, log, tmp_path, monkeypatch):
    """Records without ``programs`` (the parent), no log at all, a ring
    that overwrote set-up: nothing read, no ``[compile]`` line, nothing
    raised."""
    bare = [{k: v for k, v in r.items() if k != "programs"} for r in LOG]
    monkeypatch.setattr(_oplog, "fetch", {
        "no_programs": lambda: (bare, 0), "no_log": lambda: None,
        "overwritten": lambda: (LOG[2:], 2)}[log])
    ctx, lines = ctx_of(tmp_path)
    assert read(name, ctx) is None
    assert not [ln for ln in lines if ln.startswith("[compile] ")]


def test_the_new_entries_name_files_and_cells_that_are_there():
    bench = harness.load_benchmark(ROOT)
    cells = [w["name"] for w in bench["workloads"]]
    entries = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    # appended together, in this order
    at = names.index(METRICS[0])
    assert names[at:at + len(METRICS)] == METRICS
    for name in METRICS:
        path = harness.find_file(ROOT, bench["paths"], "metrics",
                                 name + ".py")
        assert callable(harness.load_module(path).read)
        assert {k: v for k, v in entries[name].items() if k != "name"} == {
            "unit": "s", "better": "lower", "source": "program_counter",
            "layer": "compile", "moves": "setup_s", "workloads": cells[:11]}
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "metrics",
                                       "_compile_ledger.py"))


@pytest.mark.parametrize("mix", ["tiny-boost", "tiny-ingest"])
def test_a_traced_run_reports_its_compiles(mix, tmp_path, monkeypatch):
    """End to end on the CPU at toy size, the readers as the harness finds
    them: every second they read lies inside the set-up the run prints,
    the parts add up, and the lines name programs and spans."""
    import jax

    from dmlc_core_tpu.utils import profiler

    jax.clear_caches()      # what an earlier test compiled compiles again
    monkeypatch.setattr(profiler, "_log", collections.deque(
        maxlen=profiler.OP_LOG_RECORDS))
    monkeypatch.setattr(profiler, "_log_appended", 0)
    bench = harness.load_benchmark(ROOT)
    entries = [{k: v for k, v in m.items() if k != "workloads"}
               for m in bench["per_layer"] if m["name"] in METRICS]
    root = util.make_root(tmp_path, per_layer=entries)
    planes = {
        "/device:TPU:0": {xplane.OPS_LINE: [("f.1", 1.0, 2.0)],
                          xplane.MODULES_LINE: [("jit_a(1)", 1.0, 2.0)]},
        "/host:CPU": {"main": [("bench.window", 0.0, 4.0),
                               ("bench.op", 0.5, 2.5)]}}
    monkeypatch.setattr(xplane, "load", lambda path: planes)
    lines = []
    out = harness.run_cell(root, "tiny." + mix, 2**31 + 53, 0.3, True,
                           require_chip=False, say=lines.append)
    assert out["correct"] is True, lines
    got = {k: out["metrics"][k]["value"] for k in METRICS}
    assert all(v >= 0 for v in got.values())
    said = [ln for ln in lines if ln.startswith("[compile] ")]
    total = (got["setup.compile_trace_lower_s"]
             + got["setup.compile_cache_read_s"]
             + got["setup.compile_key_or_xla_s"])
    assert said and total > 0
    assert got["setup.compile_inline_s"] <= total * (1 + 1e-9)
    # the lines carry what the readers summed
    assert sum(float(ln.split()[-7]) + float(ln.split()[-5])
               for ln in said) == pytest.approx(
        got["setup.compile_trace_lower_s"], abs=1e-3 * len(said))
    assert any(" under dmlc.ingest" in ln and " own " in ln for ln in said)
    (setup,) = [ln for ln in lines if ln.startswith("[bench] set-up ")]
    assert got["setup.compile_inline_s"] < float(setup.split()[2])
