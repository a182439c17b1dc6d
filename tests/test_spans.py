"""The program's own marks: ``profiler.span`` host phases in the
profiler's trace, in the per-operation record (``profiler.op_log``:
always, trace or no trace) and in the Chrome tracer while ``DMLC_TRACE``
is on; ``jax.named_scope`` device phases in the compiled programs, kernel
names on the Pallas calls.  The names are a contract
(doc/observability.md): the benchmark's per-layer readers find the phases
by them.
"""

import collections
import glob
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmlc_core_tpu.base import metrics
from dmlc_core_tpu.models import HistGBT
from dmlc_core_tpu.models import histgbt as G
from dmlc_core_tpu.ops import histogram as H
from dmlc_core_tpu.ops.quantile import (apply_bins, apply_bins_missing,
                                        apply_bins_t, local_summary)
from dmlc_core_tpu.parallel.mesh import local_mesh
from dmlc_core_tpu.utils import profiler
from dmlc_core_tpu.utils.profiler import (global_tracer, op_log,
                                          op_log_dropped, set_tracing, span,
                                          tracing_enabled)

# operation -> (root span, its children, the spans that lie inside the
# child named first)
OPERATIONS = {
    "ingest": ("dmlc.ingest",
               ["dmlc.ingest.stream", "dmlc.ingest.host_prep",
                "dmlc.ingest.cuts.nan_scan",
                "dmlc.ingest.cuts", "dmlc.ingest.pad", "dmlc.ingest.labels"],
               ["dmlc.ingest.put", "dmlc.ingest.put_wait",
                "dmlc.ingest.bin_dispatch", "dmlc.ingest.concat"]),
    # a mesh's first ingest: a row shard a chip put in ``.cuts``, sorted
    # by columns and binned where it lies (one dispatch, no slab stream)
    "ingest_sharded": ("dmlc.ingest",
                       ["dmlc.ingest.stream", "dmlc.ingest.host_prep",
                        "dmlc.ingest.cuts.nan_scan",
                        "dmlc.ingest.cuts", "dmlc.ingest.pad",
                        "dmlc.ingest.labels", "dmlc.ingest.cuts_fetch"],
                       ["dmlc.ingest.put", "dmlc.ingest.bin_dispatch"]),
    # the same model's next handle keeps its cuts: the slab stream, each
    # piece put to and binned on the chip that owns it
    "ingest_sharded_again": ("dmlc.ingest",
                             ["dmlc.ingest.stream", "dmlc.ingest.host_prep",
                              "dmlc.ingest.host_prep.nan_scan",
                              "dmlc.ingest.pad", "dmlc.ingest.labels"],
                             ["dmlc.ingest.put", "dmlc.ingest.bin_dispatch"]),
    "fit": ("dmlc.fit",
            ["dmlc.fit.join_warmup", "dmlc.fit.warm_dispatch",
             "dmlc.fit.dispatch", "dmlc.fit.fetch_chunk", "dmlc.fit.sync"],
            []),
    "predict": ("dmlc.predict",
                ["dmlc.predict.fetch", "dmlc.predict.stack",
                 "dmlc.predict.put", "dmlc.predict.dispatch"],
                ["dmlc.predict.fetch.wait", "dmlc.predict.fetch.copy"]),
}
# operation -> the counts its record keeps
RECORD_COUNTS = {
    "ingest": {"rows": 3000, "features": 5, "missing": 0,
               "missing_share": 0.0, "nan_scan": "device",
               "cuts_sort": "whole"},
    "ingest_sharded": {"rows": 3000, "features": 5, "missing": 0,
                       "missing_share": 0.0, "nan_scan": "device",
                       "cuts_sort": "features"},
    "ingest_sharded_again": {"rows": 3000, "features": 5, "missing": 0,
                             "missing_share": 0.0, "nan_scan": "host",
                             "cuts_sort": "none"},
    "fit": {"rounds": 4, "mesh_devices": 1},
    "predict": {"rows": 100, "programs": 1},
}


def _empty_log(mp, records=profiler.OP_LOG_RECORDS):
    """An empty ring of the record for as long as ``mp`` lasts: what
    other tests of this process logged stays out of the way, and comes
    back."""
    mp.setattr(profiler, "_log", collections.deque(maxlen=records))
    mp.setattr(profiler, "_log_appended", 0)
    mp.setattr(profiler, "_open_records", {})


@pytest.fixture
def empty_log(monkeypatch):
    _empty_log(monkeypatch)


def _events(logdir):
    """Every ``dmlc.*`` host event of the newest trace under ``logdir``
    as (name, start_ns, end_ns, stats)."""
    from jax.profiler import ProfileData

    pb = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)[-1]
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             {k: v for k, v in ev.stats})
            for plane in ProfileData.from_file(pb).planes
            for line in plane.lines for ev in line.events
            if ev.name.startswith("dmlc.")]


@pytest.fixture(scope="module")
def traced_and_logged(tmp_path_factory):
    """Each operation once, at a tiny size, under its own CPU profiler
    trace: operation -> (its ``dmlc.*`` events, what it added to
    ``op_log()``)."""
    rng = np.random.default_rng(0)
    X = rng.normal(size=(3000, 5)).astype(np.float32)
    y = (X[:, 0] * X[:, 1] > 0).astype(np.float32)
    one = HistGBT(n_trees=4, max_depth=3, n_bins=16, mesh=local_mesh(1))
    many = HistGBT(n_trees=4, max_depth=3, n_bins=16)
    handle = {}

    def ingest(model):
        # the worker's span is written when its compile ends: wait for
        # it inside the trace (fit_device joins the same handle again)
        out = model.make_device_data(X, y)
        model._pending_warmup.join()
        return out

    ops = {
        "ingest": lambda: handle.update(ingest(one)),
        "ingest_sharded": lambda: ingest(many),
        "ingest_sharded_again": lambda: ingest(many),
        "fit": lambda: one.fit_device(handle),
        "predict": lambda: one.predict(X[:100]),
    }
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    out = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("DMLC_INGEST_CHUNK_ROWS", "1000")     # three slabs
        _empty_log(mp)
        for name, op in ops.items():
            logdir = str(tmp_path_factory.mktemp("trace_" + name))
            before = len(op_log())
            jax.profiler.start_trace(logdir, profiler_options=opts)
            try:
                op()
            finally:
                jax.profiler.stop_trace()
            out[name] = (_events(logdir), op_log()[before:])
    return out


@pytest.fixture(scope="module")
def traced(traced_and_logged):
    return {name: events for name, (events, _log)
            in traced_and_logged.items()}


@pytest.mark.parametrize("operation", list(OPERATIONS))
def test_host_spans_of_one_operation(traced, operation):
    root_name, children, grandchildren = OPERATIONS[operation]
    events = traced[operation]
    names = {e[0] for e in events}
    assert {root_name, *children, *grandchildren} <= names
    (root,) = [e for e in events if e[0] == root_name]
    op = root[3]["op"]
    # one identifier for the whole operation, the compile worker's
    # thread included
    assert {e[3]["op"] for e in events} == {op}
    inside = [e for e in events if e[0] not in (root_name, "dmlc.compile")]
    assert all(root[1] <= e[1] and e[2] <= root[2] for e in inside)
    if grandchildren:
        (mid,) = [e for e in events if e[0] == children[0]]
        below_mid = [e for e in events if e[0] in grandchildren]
        if operation in ("ingest", "ingest_sharded"):
            # the matrix the cuts are computed from is put too, in
            # ``.cuts`` and before any slab of the stream (PR 43: every
            # path goes through ``_put_matrix``): whole on one chip, a
            # row shard a chip on a mesh, which no stream puts again
            (cuts,) = [e for e in events if e[0] == "dmlc.ingest.cuts"]
            cut_puts = [e for e in below_mid
                        if cuts[1] <= e[1] and e[2] <= cuts[2]]
            ndev = 1 if operation == "ingest" else len(jax.devices())
            assert [e[0] for e in cut_puts] == ["dmlc.ingest.put"] * ndev
            assert (sum(e[3]["bytes"] for e in cut_puts)
                    == cuts[3]["bytes"] == 3000 * 5 * 4)
            for e in cut_puts:
                below_mid.remove(e)
            assert bool(below_mid) and (operation == "ingest") == any(
                e[0] == "dmlc.ingest.put" for e in below_mid)
        assert all(mid[1] <= e[1] and e[2] <= mid[2] for e in below_mid)
    if operation.startswith("ingest"):
        assert root[3]["rows"] == 3000 and root[3]["features"] == 5
        (stream,) = [e for e in events if e[0] == "dmlc.ingest.stream"]
        assert stream[3]["slabs"] == (1 if operation == "ingest_sharded"
                                      else 3)
        # (a model's next handle finds the round program's compile
        # already started: no worker, no span)
        comps = [e for e in events if e[0] == "dmlc.compile"]
        assert len(comps) == (0 if operation == "ingest_sharded_again" else 1)
        assert all(c[3]["program"] == "kfn"
                   and c[3]["cache"] in ("hit", "miss") for c in comps)


@pytest.mark.parametrize("operation", list(OPERATIONS))
def test_one_record_of_one_operation(traced_and_logged, operation):
    """``op_log()`` holds ONE record an operation, joined to the trace by
    ``op``: every span the trace shows below the operation's top-level
    span is in the record's ``children`` under its name, as many times
    and with as many bytes."""
    root_name, children, grandchildren = OPERATIONS[operation]
    events, log = traced_and_logged[operation]
    (rec,) = [r for r in log if r["name"] == root_name]
    (root,) = [e for e in events if e[0] == root_name]
    assert rec["op"] == root[3]["op"]
    assert rec["counts"] == RECORD_COUNTS[operation]
    assert rec["end"] - rec["start"] > 0
    below = [e for e in events if e[0] not in (root_name, "dmlc.compile")]
    assert set(rec["children"]) - {"dmlc.compile"} == {e[0] for e in below}
    assert {*children, *grandchildren} <= set(rec["children"])
    for name in {e[0] for e in below}:
        n, seconds, longest, nbytes = rec["children"][name]
        same = [e for e in below if e[0] == name]
        assert n == len(same)
        assert nbytes == sum(e[3].get("bytes", 0) for e in same)
        assert 0 < longest <= seconds <= rec["end"] - rec["start"]
    if operation == "ingest":
        # three slabs and, before them, the matrix the cuts read
        assert rec["children"]["dmlc.ingest.put"][0] == 3 + 1
        # a wait carries the bytes of the slab it waits for
        assert (rec["children"]["dmlc.ingest.put_wait"][3]
                == rec["children"]["dmlc.ingest.put"][3] - 3000 * 5 * 4 > 0)
    if operation == "ingest_sharded":
        # a row shard a chip, every row put once
        assert rec["children"]["dmlc.ingest.put"][0::3] == [
            len(jax.devices()), 3000 * 5 * 4]
    if operation in ("ingest", "ingest_sharded"):
        # the worker's compile carries the ingest's ``op``: folded into
        # its record if it ended first, else a record of its own
        own = [r for r in log if r["name"] == "dmlc.compile"]
        assert all(r["op"] == rec["op"] for r in own)
        assert len(own) + rec["children"].get(
            "dmlc.compile", [0])[0] == 1
        verdicts = [r["compile"] for r in (rec, *own)]
        assert sum(v["hit"] + v["miss"] for v in verdicts) == 1
        assert sum(v["seconds"] for v in verdicts) > 0
        assert [r["counts"]["program"] for r in own] == ["kfn"] * len(own)
    else:
        assert [r["name"] for r in log] == [root_name]


def _compile_on_a_worker(op, started=None, go=None):
    def work():
        if started is not None:
            started.set()
            assert go.wait(10)
        with span("dmlc.compile", op=op, what="test", program="k") as sp:
            sp.set(cache="hit")

    worker = threading.Thread(target=work)
    worker.start()
    return worker


def test_a_worker_span_lands_under_its_op(empty_log):
    """A span on another thread that joined by ``op=`` is folded into its
    operation's record while that is open, and is a record of its own,
    under the same ``op``, once the operation has closed: no span is
    lost."""
    started, go = threading.Event(), threading.Event()
    with span("dmlc.test.op", rows=1) as top:
        op = top.counts["op"]
        early = _compile_on_a_worker(op)
        early.join(10)
        late = _compile_on_a_worker(op, started, go)
        assert started.wait(10)
    go.set()
    late.join(10)
    assert not early.is_alive() and not late.is_alive()
    first, second = op_log()
    assert (first["name"], first["op"]) == ("dmlc.test.op", op)
    n, seconds, longest, nbytes = first["children"]["dmlc.compile"]
    assert (n, nbytes) == (1, 0) and seconds == longest > 0
    assert first["compile"] == {"hit": 1, "miss": 0, "seconds": seconds}
    assert (second["name"], second["op"]) == ("dmlc.compile", op)
    assert second["counts"] == {"what": "test", "program": "k",
                                "cache": "hit"}
    assert second["children"] == {}
    assert second["compile"] == {"hit": 1, "miss": 0,
                                 "seconds": second["end"] - second["start"]}


def test_many_workers_fold_into_one_record(empty_log):
    """More workers than cores, all joining one open operation while its
    own thread folds children too: every span is counted."""
    import sys

    workers, each = 16, 200

    def work(op):
        for _ in range(each):
            with span("dmlc.compile", op=op, bytes=1) as sp:
                sp.set(cache="miss")

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with span("dmlc.test.op") as top:
            threads = [threading.Thread(target=work,
                                        args=(top.counts["op"],))
                       for _ in range(workers)]
            for t in threads:
                t.start()
            for _ in range(each):
                with span("dmlc.test.op.child", bytes=2):
                    pass
            for t in threads:
                t.join(30)
            assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(was)
    (rec,) = op_log()
    assert rec["children"]["dmlc.compile"][0] == workers * each
    assert rec["children"]["dmlc.compile"][3] == workers * each
    assert rec["compile"]["miss"] == workers * each
    assert rec["children"]["dmlc.test.op.child"][0::3] == [each, 2 * each]


def test_the_ring_overwrites_and_counts_what_it_dropped(monkeypatch):
    _empty_log(monkeypatch, records=8)
    for i in range(11):
        with span("dmlc.test.op", i=i):
            pass
    assert op_log_dropped() == 3
    assert [r["counts"]["i"] for r in op_log()] == list(range(3, 11))
    assert profiler.OP_LOG_RECORDS == 16_384       # no knob: a constant


def test_metrics_off_records_nothing(empty_log):
    was = metrics.enabled()
    try:
        metrics.set_enabled(False)
        with span("dmlc.test.op") as top:
            with span("dmlc.test.op.child"):
                pass
        assert op_log() == [] and op_log_dropped() == 0
        assert top.seconds > 0          # a span's own wall is always there
    finally:
        metrics.set_enabled(was)
    with span("dmlc.test.op"):
        pass
    assert [r["name"] for r in op_log()] == ["dmlc.test.op"]


def test_sharded_ingest_spans_name_their_chip(traced):
    """On a mesh a put and the binning it feeds belong to ONE chip: the
    spans say which (``chip=k``, the position on the ``data`` axis), so a
    four-chip trace can be read chip by chip.  The one-chip ingest has
    no chip to name."""
    def chips(operation, name):
        return [e[3].get("chip") for e in traced[operation] if e[0] == name]

    ndev = len(jax.devices())
    # a first ingest puts every chip its row shard, paced, and ONE
    # program over the mesh bins them where they lie
    assert chips("ingest_sharded", "dmlc.ingest.put") == list(range(ndev))
    assert chips("ingest_sharded", "dmlc.ingest.put_wait") == list(
        range(ndev))
    assert chips("ingest_sharded", "dmlc.ingest.bin_dispatch") == [None]
    # the slab stream (the cuts kept): 3000 rows in 1000-row slabs over
    # 375-row shards: every chip owns a piece, a slab is cut where it
    # straddles a boundary
    puts = chips("ingest_sharded_again", "dmlc.ingest.put")
    assert set(puts) == set(range(ndev)) and len(puts) > ndev
    assert puts == sorted(puts)                    # global row order
    assert chips("ingest_sharded_again", "dmlc.ingest.bin_dispatch") == puts
    assert set(chips("ingest", "dmlc.ingest.put")) == {None}
    assert set(chips("ingest", "dmlc.ingest.bin_dispatch")) == {None}


def test_fit_span_names_its_mesh(traced):
    (fit,) = [e for e in traced["fit"] if e[0] == "dmlc.fit"]
    assert fit[3]["mesh_devices"] == 1 and fit[3]["rounds"] == 4


def test_two_operations_carry_two_ops(traced):
    ops = {name: {e[3]["op"] for e in evs} for name, evs in traced.items()}
    assert all(len(v) == 1 for v in ops.values())
    assert len(set.union(*ops.values())) == len(traced)


def test_span_records_to_the_tracer_only_while_tracing(empty_log):
    was = tracing_enabled()
    tr = global_tracer()
    try:
        set_tracing(False)
        tr.clear()
        with span("dmlc.test.phase", rows=7):
            pass
        assert tr.events() == []
        # ... and to the record of its operation with ``DMLC_TRACE`` unset
        (rec,) = op_log()
        assert (rec["name"], rec["counts"]) == ("dmlc.test.phase",
                                                {"rows": 7})
        set_tracing(True)
        with span("dmlc.test.phase", rows=7) as outer:
            with span("dmlc.test.phase.child") as inner:
                inner.set(cache="hit")
        child, phase = tr.events()          # a child completes first
        assert (phase["name"], phase["ph"]) == ("dmlc.test.phase", "X")
        assert phase["args"] == {"rows": 7, "op": outer.counts["op"]}
        assert child["name"] == "dmlc.test.phase.child"
        assert child["args"] == {"op": outer.counts["op"], "cache": "hit"}
        assert phase["ts"] <= child["ts"]
        assert child["ts"] + child["dur"] <= phase["ts"] + phase["dur"]
        rec = op_log()[1]
        assert rec["op"] == outer.counts["op"] and rec["counts"] == {"rows": 7}
        assert list(rec["children"]) == ["dmlc.test.phase.child"]
    finally:
        set_tracing(was)
        tr.clear()


def _round_program_text():
    rng = np.random.default_rng(1)
    X = rng.normal(size=(512, 4)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    m = HistGBT(n_trees=2, max_depth=3, n_bins=16)
    h = m.make_device_data(X, y)
    fn = m._build_round_fn(m._round_plan(4), 2)
    return fn.lower(h["bins_t"], h["y_d"], h["w_d"],
                    m._init_margin_device(h["n_padded"])).compile().as_text()


_PROGRAMS = {
    "apply_bins": (
        lambda: apply_bins.lower(jnp.zeros((64, 4)), jnp.zeros((4, 15))
                                 ).compile().as_text(),
        ["dmlc.bin"]),
    "local_summary": (
        lambda: local_summary.lower(jnp.zeros((64, 4)), None, 16, False
                                    ).compile().as_text(),
        ["dmlc.cuts"]),
    "_predict_trees": (
        lambda: G._predict_trees.lower(
            jnp.zeros((64, 4), jnp.uint8), jnp.zeros((2, 3, 4), jnp.int32),
            jnp.zeros((2, 3, 4), jnp.int32), jnp.zeros((2, 8)), 3
        ).compile().as_text(),
        ["dmlc.descend"]),
    "_predict_slab": (
        lambda: G._predict_slab.lower(
            jnp.zeros((64, 4)), jnp.zeros((4, 15)),
            [{"feat": jnp.zeros((2, 3, 4), jnp.int32),
              "thr": jnp.zeros((2, 3, 4), jnp.int32),
              "leaf": jnp.zeros((2, 8))}] * 2, 3, -1, 0.5,
            G._Logistic.transform).compile().as_text(),
        ["dmlc.bin", "dmlc.descend"]),
    "round_program": (
        _round_program_text,
        ["dmlc.round.grad", "dmlc.round.leaf", "dmlc.round.update"] + [
            f"dmlc.round.L{d}.{phase}" for d in range(3)
            for phase in ("hist", "sync", "split")]),
}


@pytest.mark.parametrize("program", list(_PROGRAMS))
def test_compiled_programs_carry_their_scopes(program):
    compiled_text, scopes = _PROGRAMS[program]
    text = compiled_text()
    op_names = [line.split('op_name="', 1)[1].split('"', 1)[0]
                for line in text.splitlines() if 'op_name="' in line]
    for scope in scopes:
        assert any(scope in name.split("/") for name in op_names), scope


def test_the_cut_sort_is_of_the_keys_alone():
    """ISSUE 38: ``local_summary`` asks for an UNSTABLE sort of one
    operand (a stable one makes the TPU compiler sort a row index beside
    the keys), and the sort still sits under ``dmlc.cuts``, where
    ``ingest.cuts_device_s`` reads it."""
    lowered = local_summary.lower(jnp.zeros((64, 4)), None, 16, False)
    sorts = [line for line in lowered.as_text().splitlines()
             if "stablehlo.sort" in line]
    assert len(sorts) == 1 and "is_stable = false" in sorts[0], sorts
    assert sorts[0].split("stablehlo.sort", 1)[1].count("%") == 1, sorts[0]
    compiled = [line for line in lowered.compile().as_text().splitlines()
                if " sort(" in line]
    assert len(compiled) == 1, compiled
    op_name = compiled[0].split('op_name="', 1)[1].split('"', 1)[0]
    assert "dmlc.cuts" in op_name.split("/"), compiled[0]


# a 20,000 x 28 slab against 255 and 1023 cuts, the widths a fit bins at
_BIN_ARGS = {n_cuts: (jax.ShapeDtypeStruct((20_000, 28), jnp.float32),
                      jax.ShapeDtypeStruct((28, n_cuts), jnp.float32))
             for n_cuts in (255, 1023)}
_BIN_PROGRAMS = {
    "apply_bins": lambda a: apply_bins.lower(*a),
    "apply_bins_missing": lambda a: apply_bins_missing.lower(*a, 255),
    "apply_bins_t": lambda a: apply_bins_t.lower(*a),
    "_bin_chunk_fn": lambda a: G._bin_chunk_fn(local_mesh(), None).lower(*a),
    "_bin_chunk_fn.missing": lambda a: G._bin_chunk_fn(local_mesh(),
                                                       255).lower(*a),
}


@pytest.mark.parametrize("n_cuts", list(_BIN_ARGS))
@pytest.mark.parametrize("program", list(_BIN_PROGRAMS))
def test_binning_is_one_count_over_the_cuts(program, n_cuts):
    """Binning stays a compare-and-count (PERF.md section 6, PR 28): no
    binary search comes back (a ``gather`` inside a ``while``: 245x the
    count's time on the chip), the ``dmlc.bin`` scope is on the
    reduce, and the CPU backend fuses the compare into it instead of
    holding the [F, C, n] intermediate (572 MB at this size)."""
    lowered = _BIN_PROGRAMS[program](_BIN_ARGS[n_cuts])
    compiled = lowered.compile()
    for text in (lowered.as_text(), compiled.as_text()):
        assert "gather" not in text and "while" not in text
    reduces = [line for line in compiled.as_text().splitlines()
               if " reduce(" in line]
    assert reduces
    for line in reduces:
        op_name = line.split('op_name="', 1)[1].split('"', 1)[0]
        assert "dmlc.bin" in op_name.split("/"), line
    # arguments and result are 2.3 MB + 0.6 MB (2.2 MB as int32)
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


def test_the_pallas_kernel_is_named():
    n, F, B, T = 512, 8, 16, 256
    bins = jnp.zeros((F, n), jnp.uint8)
    node = jnp.zeros(n, jnp.int32)
    g = jnp.ones(n, jnp.float32)
    assert "name=dmlc_hist\n" in str(jax.make_jaxpr(
        lambda: H._hist_pallas(bins, node, g, g, 1, B, T, 0, True, None))())
