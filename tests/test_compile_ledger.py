"""The compile ledger: every program jax traces, lowers, compiles or reads
back from the persistent cache is ONE entry of the ``programs`` of the
operation open on the compiling thread (``base/compile_cache.py``'s
listeners, ``profiler.fold_program``), with its own seconds and its own
cache verdict; what compiles outside any operation is tallied, so that no
second jax reported is lost and none is counted twice."""

import collections
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax._src import monitoring

from dmlc_core_tpu.base import compile_cache as cc
from dmlc_core_tpu.utils import profiler
from dmlc_core_tpu.utils.profiler import fold_program, op_log, span

PHASES = ("trace_s", "lower_s", "backend_s")
X = np.ones(8, np.float32)


@pytest.fixture
def tmp_cache(tmp_path):
    """A fresh persistent cache; the harness's own comes back after."""
    prev = jax.config.jax_compilation_cache_dir
    cc.set_cache_dir(str(tmp_path / "xla_cache"))
    try:
        yield
    finally:
        cc.set_cache_dir(prev)


@pytest.fixture
def empty_log(monkeypatch):
    """An empty ring, once the compile workers an earlier test left
    running have closed their records into the old one."""
    for worker in threading.enumerate():
        if worker.name.startswith("compile-"):
            worker.join(120)
            assert not worker.is_alive()
    monkeypatch.setattr(profiler, "_log", collections.deque(
        maxlen=profiler.OP_LOG_RECORDS))
    monkeypatch.setattr(profiler, "_log_appended", 0)
    monkeypatch.setattr(profiler, "_open_records", {})


@pytest.fixture
def reported():
    """Every phase jax reports while the test runs, as (thread, event's
    last word, program, seconds)."""
    got = []

    def listen(event, start, end, fun_name="", **kw):
        if event in cc._PHASES:
            got.append((threading.get_ident(), cc._PHASES[event], fun_name,
                        end - start))

    monitoring.register_event_time_span_listener(listen)
    try:
        yield got
    finally:
        monitoring.unregister_event_time_span_listener(listen)


def program(salt, steps=40):
    """A function no test has traced: its own name, its own constants."""
    def fn(x):
        for i in range(steps):
            x = jnp.sin(x) * (salt + i)
        return x
    fn.__name__ = f"ledger_{salt}"
    return fn


def of(records, name):
    return [p for r in records for p in r["programs"]
            if p["program"] == f"jit({name})"]


def test_a_miss_then_a_hit_of_one_program(tmp_cache, empty_log):
    fn = program(1)
    with span("dmlc.test.cold"):
        jax.jit(fn)(X)
    jax.clear_caches()
    with span("dmlc.test.warm"):
        jax.jit(fn)(X)
    cold, warm = op_log()
    (miss,), (hit,) = of([cold], "ledger_1"), of([warm], "ledger_1")
    assert (miss["verdict"], hit["verdict"]) == ("miss", "hit")
    assert miss["read_s"] == 0.0 < hit["read_s"] <= hit["backend_s"]
    for entry in (miss, hit):
        assert all(entry[k] > 0 for k in PHASES)
        assert set(entry) == {"program", *PHASES, "read_s", "verdict",
                              "thread", "under"}


def test_an_inline_jit_lands_in_its_operation_under_its_span(tmp_cache,
                                                             empty_log):
    with span("dmlc.ingest", rows=8) as top:
        with span("dmlc.ingest.cuts"):
            jax.jit(program(2))(X)
        jax.jit(program(3))(X)
    (rec,) = op_log()
    assert rec["op"] == top.counts["op"]
    assert [(p["program"], p["thread"], p["under"])
            for p in rec["programs"]] == [
        ("jit(ledger_2)", "own", "dmlc.ingest.cuts"),
        ("jit(ledger_3)", "own", "dmlc.ingest")]
    inside = rec["children"]["dmlc.ingest.cuts"][1]
    assert 0 < sum(rec["programs"][0][k] for k in PHASES) <= inside


def test_a_workers_job_is_joined_under_its_span(tmp_cache, empty_log):
    fn = program(4, steps=400)
    with span("dmlc.fit"):
        bg = cc.BackgroundCompiler(
            {"kfn": lambda: jax.jit(fn).lower(X).compile()}, what="test")
        bg.join()
    (rec,) = op_log()
    (entry,) = rec["programs"]
    assert (entry["program"], entry["thread"], entry["under"],
            entry["verdict"]) == ("jit(ledger_4)", "joined", "dmlc.compile",
                                  "miss")
    n, wall, _longest, _bytes = rec["children"]["dmlc.compile"]
    assert n == 1 and 0.7 * wall <= sum(entry[k] for k in PHASES) <= wall
    assert rec["compile"] == {"hit": 0, "miss": 1, "seconds": wall}
    assert bg.cache_verdict == "miss"


def test_two_workers_at_once_carry_each_its_own_verdict(tmp_cache,
                                                        empty_log):
    """One program cached and one not, compiling side by side: the span of
    each says what ITS thread's compile did, not what the process saw."""
    cached, fresh = program(5), program(6)
    jax.jit(cached).lower(X).compile()
    jax.clear_caches()
    both = threading.Barrier(2, timeout=60)

    def job(fn):
        def thunk():
            both.wait()
            out = jax.jit(fn).lower(X).compile()
            both.wait()         # neither span closes before both compiled
            return out
        return thunk

    with span("dmlc.fit"):
        bg = cc.BackgroundCompiler({"a": job(cached), "b": job(fresh)},
                                   what="test")
        bg.join()
    (rec,) = op_log()
    assert rec["compile"]["hit"] == rec["compile"]["miss"] == 1
    assert {p["program"]: (p["verdict"], p["thread"], p["read_s"] > 0)
            for p in rec["programs"]} == {
        "jit(ledger_5)": ("hit", "joined", True),
        "jit(ledger_6)": ("miss", "joined", False)}
    assert bg.cache_verdict == "miss"       # the worst of the two


def test_a_worker_that_outlives_its_operation_keeps_its_programs(
        tmp_cache, empty_log):
    go = threading.Event()

    def thunk():
        assert go.wait(60)
        return jax.jit(program(7)).lower(X).compile()

    with span("dmlc.ingest") as top:
        bg = cc.BackgroundCompiler({"kfn": thunk}, what="test")
    go.set()
    bg.join()
    first, own = op_log()
    assert first["programs"] == []
    assert (own["name"], own["op"]) == ("dmlc.compile", top.counts["op"])
    (entry,) = own["programs"]
    assert (entry["program"], entry["thread"], entry["under"]) == (
        "jit(ledger_7)", "joined", "dmlc.compile")
    # the span carries its own thunk's phases beside ``what`` and
    # ``program``, and they are the entry's
    counts = own["counts"]
    assert {"what", "program", "cache", *PHASES, "read_s"} == set(counts)
    assert (counts["what"], counts["program"], counts["cache"]) == (
        "test", "kfn", "miss")
    assert all(counts[k] == pytest.approx(entry[k], abs=1e-6)
               for k in PHASES)


def test_nested_jits_count_a_traced_second_once(tmp_cache, empty_log,
                                                reported):
    inner_a, inner_b = jax.jit(program(8)), jax.jit(program(9))

    def outer(x):
        return inner_a(x) + inner_b(x)
    outer.__name__ = "ledger_outer"

    nested = cc.stats()["nested_traces"]
    with span("dmlc.test.op"):
        jax.jit(outer)(X)
    (rec,) = op_log()
    (entry,) = rec["programs"]          # the inner jits are no programs
    assert entry["program"] == "jit(ledger_outer)"
    traces = {name: s for _t, phase, name, s in reported
              if phase == "trace_s"}
    assert {"ledger_8", "ledger_9", "ledger_outer"} <= set(traces)
    assert entry["trace_s"] == traces["ledger_outer"]
    after = cc.stats()["nested_traces"]
    # every other trace (the inner jits, each ``jnp`` call) lies inside it
    assert after["n"] - nested["n"] == sum(
        1 for _t, phase, _n, _s in reported if phase == "trace_s") - 1
    assert after["seconds"] - nested["seconds"] == pytest.approx(
        sum(s for _t, phase, _n, s in reported if phase == "trace_s")
        - entry["trace_s"])


def test_a_lowering_is_an_entry_and_its_compile_completes_it(tmp_cache,
                                                             empty_log):
    with span("dmlc.test.lower_only"):
        jax.jit(program(10)).lower(X)
    with span("dmlc.test.both"):
        lowered = jax.jit(program(11)).lower(X)
        lowered.compile()
    with span("dmlc.test.lowered"):
        later = jax.jit(program(12)).lower(X)
    with span("dmlc.test.compiled"):
        later.compile()
    only, both, lowered_rec, compiled_rec = op_log()
    (entry,) = only["programs"]
    assert entry["trace_s"] > 0 and entry["lower_s"] > 0
    assert (entry["backend_s"], entry["verdict"]) == (0.0, "none")
    (entry,) = both["programs"]
    assert all(entry[k] > 0 for k in PHASES) and entry["verdict"] == "miss"
    # an operation that closed in between keeps what it saw
    (first,), (second,) = lowered_rec["programs"], compiled_rec["programs"]
    assert first["program"] == second["program"] == "jit(ledger_12)"
    assert first["lower_s"] > 0 and first["backend_s"] == 0.0
    assert second["backend_s"] > 0 and second["lower_s"] == 0.0


def test_a_trace_nothing_lowers_is_an_entry_of_its_own(tmp_cache, empty_log):
    with span("dmlc.test.op"):
        jax.eval_shape(jax.jit(program(13)), X)
        jax.jit(program(14))(X)
    (rec,) = op_log()
    shape_only, compiled = rec["programs"]
    assert shape_only["program"] == "ledger_13"
    assert shape_only["trace_s"] > 0
    assert shape_only["lower_s"] == shape_only["backend_s"] == 0.0
    assert compiled["program"] == "jit(ledger_14)"


def test_a_lowering_jax_has_no_name_for_takes_its_traces(empty_log):
    """The pieces of a sharded put lower as ``jit(<unknown>)`` right after
    a trace named ``empty``: one program, under the trace's name."""
    trace, lower, backend = cc._PHASES
    with span("dmlc.test.op"):
        cc._on_time_span(trace, 1024.0, 1025.0, fun_name="empty")
        cc._on_time_span(lower, 1025.0, 1025.5, fun_name="jit(<unknown>)")
        cc._on_time_span(backend, 1025.5, 1027.5, fun_name="jit(<unknown>)")
    (rec,) = op_log()
    assert rec["programs"] == [{
        "program": "jit(empty)", "trace_s": 1.0, "lower_s": 0.5,
        "backend_s": 2.0, "read_s": 0.0, "verdict": "none", "thread": "own",
        "under": "dmlc.test.op"}]


def test_a_compile_outside_any_operation_is_tallied(tmp_cache, empty_log,
                                                    reported):
    before = cc.stats()["unowned"]
    jax.jit(program(15))(X)
    after = cc.stats()["unowned"]
    assert op_log() == []
    assert after["n"] - before["n"] == 1
    assert after["seconds"] - before["seconds"] == pytest.approx(
        sum(s for _t, _phase, name, s in reported if "ledger_15" in name))


def test_every_reported_second_is_in_a_record_or_a_tally(tmp_cache,
                                                         empty_log, reported):
    """Records + ``unowned`` + ``nested_traces`` = what jax reported, on
    the calling thread and on the workers'."""
    before = cc.stats()
    inner = jax.jit(program(16))
    jax.jit(program(17))(X)                              # unowned
    with span("dmlc.ingest"):
        bg = cc.BackgroundCompiler(
            {"kfn": lambda: jax.jit(program(18)).lower(X).compile()},
            what="test")
        jax.jit(lambda x: inner(x) * 2)(X)               # own, nested
        jax.jit(program(19)).lower(X)                    # never compiled
    bg.join()
    with span("dmlc.test.flush"):
        jax.jit(program(20))(X)
    after = cc.stats()
    in_records = sum(p[k] for r in op_log() for p in r["programs"]
                     for k in PHASES)
    tallied = sum(after[k]["seconds"] - before[k]["seconds"]
                  for k in ("unowned", "nested_traces"))
    assert in_records + tallied == pytest.approx(
        sum(s for _t, _phase, _name, s in reported))


@pytest.mark.parametrize("agree", [True, False])
def test_the_65th_entry_folds_into_more(empty_log, agree):
    extra = 6
    with span("dmlc.test.op"):
        for i in range(profiler.OP_LOG_PROGRAMS):
            fold_program(f"p{i}", "hit", trace_s=1.0)
        with span("dmlc.test.op.child" if not agree else "dmlc.test.op"):
            for i in range(extra - 1):
                fold_program(f"q{i}", "hit", trace_s=1.0, backend_s=0.5,
                             read_s=0.25)
        opened = fold_program("q", "none", lower_s=2.0)
        assert opened["program"] == "(more)"
        # the phase that completes an entry summed there adds no entry
        fold_program("q", "miss", opened, backend_s=4.0)
    (rec,) = op_log()
    assert len(rec["programs"]) == profiler.OP_LOG_PROGRAMS + 1
    assert [p["program"] for p in rec["programs"][:-1]] == [
        f"p{i}" for i in range(profiler.OP_LOG_PROGRAMS)]
    assert rec["programs"][-1] == {
        "program": "(more)", "n": extra, "trace_s": extra - 1.0,
        "lower_s": 2.0, "backend_s": 0.5 * (extra - 1) + 4.0,
        "read_s": 0.25 * (extra - 1), "verdict": "miss", "thread": "own",
        "under": "dmlc.test.op" if agree else "mixed"}


def test_the_record_keeps_its_keys_and_gains_one(tmp_cache, empty_log):
    with span("dmlc.test.op", rows=3):
        with span("dmlc.test.op.child", bytes=5):
            jax.jit(program(21))(X)
    (rec,) = op_log()
    assert list(rec) == ["op", "name", "start", "end", "counts",
                         "children", "compile", "programs"]
    assert rec["counts"] == {"rows": 3}
    assert rec["compile"] == {"hit": 0, "miss": 0, "seconds": 0.0}
    (child,) = rec["children"].items()
    assert child[0] == "dmlc.test.op.child"
    n, seconds, longest, nbytes = child[1]
    assert (n, nbytes) == (1, 5) and seconds == longest > 0
    # a copy: the ring's own entries are not the caller's to change
    rec["programs"][0]["trace_s"] = -1.0
    assert op_log()[0]["programs"][0]["trace_s"] > 0


def test_no_operation_no_record_no_entry(empty_log):
    assert fold_program("p", "hit", trace_s=1.0) is None
    assert op_log() == []
