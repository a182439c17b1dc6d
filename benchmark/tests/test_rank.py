"""What ISSUE 44 adds to the benchmark, on the CPU at toy size: the
ranking configuration (MSLR-WEB30K under ``rank:ndcg``), its data rule,
its plain reference (a per-query loop), an operation with the accepted
boost window that hands ``qid`` to the system, a mix whose limits are the
cell's own, and four readers.  The shipped files load and run in a
scratch root as files only (their sizes cut); the mix's limits name every
number the operation's check produces; the program keeps every limit that
does not depend on the size and each control leaves one; the readers read
a synthetic trace and are silent on a program without their scope, plan
key or span.

As in ``test_missing.py``, membership in ``BENCHMARK.json``'s lists is
asserted with ``<=``, never ``==``: the file is append-only and a later
PR may put these cells on more lists.
"""

import json
import os
import shutil

import numpy as np
import pytest

import test_oplog
import test_spans
import util
from benchmark import (checks, checks_rank, datagen_rank, harness,
                       reference as ref, reference_rank as rr, xplane)
from benchmark.metrics import _oplog, _spans

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
SEED = 2**31 + 44
CONFIG = "mslr-web30k-d6"
MIX = "boost-r25-rank"
CELL = CONFIG + "." + MIX
NEW_READERS = ["round.grad_ms", "round.grad_ms.widest", "grad.pad_share",
               "setup.ingest_regroup_s"]


def shipped(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def limit(name):
    lim = shipped("traffic", MIX)["limits"][name]
    return lim["limit"] if isinstance(lim, dict) else lim


def bench_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# -- the shipped files -------------------------------------------------------------

def test_the_configuration_is_the_sources_uncut():
    cfg, deep = shipped("configs", CONFIG), shipped("configs",
                                                    "higgs-24m-d8")
    assert (cfg["queries"], cfg["rows"], cfg["features"], cfg["max_group"],
            cfg["relevance_levels"]) == (31_531, 3_771_125, 136, 1_251, 5)
    assert (cfg["objective"], cfg["max_depth"], cfg["n_bins"],
            cfg["learning_rate"], cfg["reg_lambda"],
            cfg["min_child_weight"], cfg["base_score"]) == \
        ("rank:ndcg", 6, 256, 0.1, 1.0, 0.1, 0.0)
    assert cfg["reduced"] == [] and cfg["chips"] == 1
    assert cfg["architecture"] is None
    assert cfg["n_summary"] == 8 * cfg["n_bins"]
    for promise in ("no truncation", "counts once", "whole query",
                    "byte-identical", "caller's row order"):
        assert promise in cfg["guarantees"], promise
    assert "pair sums" in cfg["precision"]
    assert cfg["precision"].endswith(deep["precision"].split("; ", 1)[1])
    assert len(cfg["source"]) <= 200 and "MSLR-WEB30K" in cfg["source"]
    assert any("chip" in a and "GiB" in a for a in cfg["assumed"])
    bench = bench_json()
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry["source"] == cfg["source"] and entry["reduced"] == []
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert len({c["source"] for c in bench["configs"]}) == \
        len(bench["configs"])


def test_the_new_cell_is_an_entry_and_a_name_in_lists():
    e2e = "boost_rounds_per_s"
    emits = set(NEW_READERS) | {
        "round.hist_ms", "round.hist_ms.deepest", "round.nonhist_ms",
        "hist.time_share", "hist.mxu_share", "dispatch.gap_ms.boost",
        "setup.fit_s", "setup.ingest_s",
        "setup.ingest_host_prep_s", "setup.ingest_stream_s",
        "setup.compile_s", "setup.compile_wait_s"}
    bench = bench_json()
    (entry,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        (CONFIG, MIX, 1)
    assert len(entry["why"]) <= 200
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert {e2e} | emits <= listed
    assert [m["name"] for m in harness.metrics_of(bench, "end_to_end",
                                                  CELL)] == [e2e, "setup_s"]
    for m in harness.metrics_of(bench, "per_layer", CELL):
        harness.find_file(ROOT, bench["paths"], "metrics", m["name"] + ".py")
        assert m["moves"] in (e2e, "setup_s"), m
    new = {m["name"]: m for m in bench["per_layer"]
           if m["name"] in NEW_READERS}
    assert [m["name"] for m in bench["per_layer"]
            if m["name"] in NEW_READERS] == NEW_READERS     # in this order
    for name in NEW_READERS[:3]:
        assert (new[name]["layer"], new[name]["moves"]) == ("objective", e2e)
        assert CELL in new[name]["workloads"]
    assert (new[NEW_READERS[3]]["layer"], new[NEW_READERS[3]]["moves"]) == \
        ("ingest", "setup_s")
    assert bench["run_seconds"] == 20
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.index(CELL) == 7                # after the accepted seven


def test_the_second_four_chip_cell_is_data_alone():
    """``higgs-d6-dp4.ingest``: a configuration and a mix that were there,
    an entry, and its name on the lists whose readers find their events
    on the mesh path (a traced run on four chips reported every one of
    them: PERF.md section 5).  A quarter of the cells, rounded down, may
    take four chips: two of nine."""
    cell = "higgs-d6-dp4.ingest"
    bench = bench_json()
    (entry,) = [w for w in bench["workloads"] if w["name"] == cell]
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        ("higgs-d6-dp4", "ingest", 4)
    assert len(entry["why"]) <= 200
    cells = bench["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= len(cells) // 4
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if cell in m.get("workloads", ())}
    assert {"ingest_rows_per_s", "setup.ingest_s", "setup.ingest_host_prep_s",
            "setup.ingest_stream_s", "setup.compile_s",
            "ingest.device_busy_share", "ingest.sort_share",
            "ingest.bin_device_s", "ingest.cuts_device_s",
            "ingest.idle_s.host_prep", "ingest.idle_s.cuts_put",
            "ingest.idle_s.stream"} <= listed
    assert not {"setup.fit_s", "setup.compile_wait_s",
                "setup.predict_s"} & listed          # an ingest fits nothing
    assert [m["name"] for m in harness.metrics_of(bench, "end_to_end", cell)
            ] == ["ingest_rows_per_s", "setup_s"]


def test_the_mix_says_where_each_limit_comes_from():
    bench = bench_json()
    mix = shipped("traffic", MIX)
    assert mix["op"] == "boost_rank"
    assert mix["end_to_end"] == {"boost_rounds_per_s": {"kind": "rate"}}
    assert mix["params"] == {
        "n_trees": 25, "warm_trees": 25, "check_bin_rows": 4096,
        "check_heldout_queries": 2048, "check_train_queries": 2048}
    assert mix["trace_seconds"] == 10
    assert set(mix["limits"]) - {"rounds_share", "rows_share"} <= \
        set(mix["limits_from"])
    assert "PR 44" in mix["limits_from"]["readings"]
    harness.find_file(ROOT, bench["paths"], "ops", "boost_rank.py")


def test_the_window_is_the_accepted_operation():
    """``ops/boost_rank.py`` is ``ops/boost.py`` but for where the rows
    come from, the ``qid`` it hands over and what the check compares:
    ``op`` (the timed part) is the same source, line for line, and there
    is no way round ``make_device_data(..., qid=...)``."""
    import inspect

    def src(name, f=None):
        mod = harness.load_module(os.path.join(BENCH, "ops", name + ".py"))
        return inspect.getsource(getattr(mod, f) if f else mod)

    assert src("boost_rank", "op") == src("boost", "op")
    whole = src("boost_rank")
    assert "model.make_device_data(X, y, qid=qid)" in whole
    assert ".fit(" not in whole.replace("model.fit_device(", "")
    # set-up's sequence: the rows, the model, the ingest, one warm fit
    setup = src("boost_rank", "setup")
    for a, b in zip(("_rows(", "system.new_model(", "_ingest(",
                     'p["warm_trees"]', "model.fit_device(handle)"),
                    ("system.new_model(", "_ingest(", 'p["warm_trees"]',
                     "model.fit_device(handle)", 'p["n_trees"])\n    ctx')):
        assert setup.index(a) < setup.index(b), (a, b)


# -- in a scratch root, as files only ------------------------------------------------

TOY = dict(queries=300, rows=12000, features=16, max_group=150, n_bins=32,
           n_summary=256, max_depth=4)


def run(root, cell, trace=False):
    lines = []
    out = harness.run_cell(root, cell, SEED, 0.3, trace, require_chip=False,
                           say=lines.append)
    return out, lines


@pytest.fixture(scope="module")
def scratch(tmp_path_factory):
    root = util.make_root(tmp_path_factory.mktemp("rank"))
    base = os.path.join(root, "bench_data")
    cfg = dict(shipped("configs", CONFIG), **TOY)
    json.dump(cfg, open(f"{base}/configs/mslr.json", "w"))
    mix = shipped("traffic", MIX)
    mix["params"] = dict(mix["params"], n_trees=3, warm_trees=3,
                         check_bin_rows=256, check_heldout_queries=64,
                         check_train_queries=64)
    # three rounds on 300 queries learn little: the toy's own limits for
    # what depends on the size, the shipped file's for the rest
    mix["limits"] = dict(
        mix["limits"],
        train_ndcg10={"limit": 0.5, "passes": "at_least"},
        heldout_ndcg10={"limit": 0.5, "passes": "at_least"})
    json.dump(mix, open(f"{base}/traffic/{MIX}.json", "w"))
    for reader in NEW_READERS:
        shutil.copy(os.path.join(BENCH, "metrics", reader + ".py"),
                    f"{base}/metrics/{reader}.py")
    bench = json.load(open(f"{root}/BENCHMARK.json"))
    bench["configs"].append({"name": "mslr", "source": cfg["source"],
                             "file": "bench_data/configs/mslr.json",
                             "reduced": [], "why": "self-test"})
    bench["workloads"].append({"name": "mslr." + MIX, "config": "mslr",
                               "traffic": MIX, "chips": 1,
                               "why": "self-test"})
    for m in bench["end_to_end"]:
        if m["name"] == "boost_rounds_per_s":
            m["workloads"].append("mslr." + MIX)
    shipped_entries = {m["name"]: m for m in bench_json()["per_layer"]}
    for reader in NEW_READERS:
        bench["per_layer"].append(dict(shipped_entries[reader],
                                       workloads=["mslr." + MIX]))
    json.dump(bench, open(f"{root}/BENCHMARK.json", "w"))
    return root, mix


def test_new_files_run_in_a_scratch_root(scratch):
    root, mix = scratch
    out, lines = run(root, "mslr." + MIX)
    assert out["correct"] is True, lines
    assert out["metrics"]["boost_rounds_per_s"]["value"] > 0
    assert set(out["metrics"]) == {"boost_rounds_per_s", "setup_s"}
    assert set(out["compared"]) == set(mix["limits"]) | {
        "window.compiles", "ops.failed"}
    assert out["compared"]["rows_share"]["value"] == 1.0
    assert out["compared"]["ops_trees_differ"]["value"] == 0


def test_a_traced_run_without_the_marks_leaves_the_new_metrics_out(
        scratch, monkeypatch):
    """A trace without the gradient's scopes, a program whose log has no
    regroup span: three readers return nothing, the line leaves the
    metrics out, nothing raises.  ``grad.pad_share`` reads the program's
    own ``round_plan``, which this program has."""
    root, _ = scratch
    planes = {"/device:TPU:0": {xplane.OPS_LINE: [("fusion.2", 1.0, 1.5)],
                                xplane.MODULES_LINE: [("jit_a(1)", 1.0,
                                                       1.5)]},
              "/host:CPU": {"main": [("bench.window", 0.0, 2.0),
                                     ("bench.op", 0.5, 2.0)]}}
    monkeypatch.setattr(xplane, "load", lambda path: planes)
    monkeypatch.setattr(_spans, "load", lambda path: _spans.Marks(
        [], [[("", 1.0, 1.5)]]))
    out, lines = run(root, "mslr." + MIX, trace=True)
    assert set(NEW_READERS) & set(out["metrics"]) == {"grad.pad_share"}, lines
    assert 0 < out["metrics"]["grad.pad_share"]["value"] < 100
    assert "compile.cache_misses" in out["metrics"]


# -- the readers ---------------------------------------------------------------------

read = test_spans.read


def test_gradient_readers_on_a_synthetic_ranking_round():
    # a scan 1..9: the gradient stage's own fusions (the gathers) 1 s,
    # two buckets 0.5 s and 1.5 s, a dense round's elementwise grad scope,
    # then a level's kernel
    ops = [("", 1.0, 9.0),
           ("dmlc.round.grad.rank", 1.0, 2.0),
           ("dmlc.round.grad.rank.w8", 2.0, 2.5),
           ("dmlc.round.grad.rank.w1280", 2.5, 4.0),
           ("dmlc.round.grad", 4.0, 4.25),
           ("dmlc.round.L0.hist", 5.0, 8.0)]
    ctx = test_spans.ctx_of(ops, [], [], ops=2, work=50.0)      # 100 rounds
    assert read(ctx, "round.grad_ms") == pytest.approx(1e3 * 3.25 / 100)
    assert read(ctx, "round.grad_ms.widest") == pytest.approx(15.0)
    # the stage is part of what the kernels leave: counted once
    assert read(ctx, "round.grad_ms") <= read(ctx, "round.nonhist_ms")
    assert read(ctx, "round.grad_ms.widest") <= read(ctx, "round.grad_ms")
    # a dense cell: the elementwise stage reads, no bucket does
    dense = test_spans.ctx_of([("", 1.0, 9.0), ("dmlc.round.grad", 1.0, 1.5),
                               ("dmlc.round.L0.hist", 2.0, 8.0)], [], [])
    assert read(dense, "round.grad_ms") == pytest.approx(5.0)
    assert read(dense, "round.grad_ms.widest") is None
    none = test_spans.ctx_of([("dmlc.round.L0.hist", 1.0, 4.0),
                              ("dmlc.round.gradient", 4.0, 5.0)], [], [])
    assert read(none, "round.grad_ms.widest") is None


def test_pad_share_reads_the_programs_own_plan():
    ctx = test_spans.ctx_of([], [], [])
    assert read(ctx, "grad.pad_share") is None
    ctx.counters["round_plan"] = {"fused_round": True}         # a dense cell
    assert read(ctx, "grad.pad_share") is None
    ctx.counters["round_plan"] = {"rank_pairs": 600, "rank_pair_slots": 800,
                                  "rank_buckets": [[8, 4], [16, 2]]}
    assert read(ctx, "grad.pad_share") == pytest.approx(25.0)


def test_regroup_reader_reads_set_ups_record(monkeypatch):
    log = [test_oplog.record(1, "dmlc.ingest", 4.0, rows=24, children={
        "dmlc.ingest.host_prep": [1, 2.2, 2.2, 0],
        "dmlc.ingest.host_prep.regroup": [1, 1.5, 1.5, 0]}),
        test_oplog.record(2, "dmlc.fit", 8.0, rounds=25),
        test_oplog.record(3, "dmlc.fit", 8.0, rounds=25)]
    spans = [("dmlc.fit", 0.1, 8.0, 3)]
    monkeypatch.setattr(_oplog, "fetch", lambda: (log, 0))
    ctx = test_spans.ctx_of([], spans, [], ops=1)
    assert read(ctx, "setup.ingest_regroup_s") == pytest.approx(1.5)
    assert read(ctx, "setup.ingest_host_prep_s") == pytest.approx(2.2)
    # a dense cell's ingest has no such span; a program without a log
    dense = [test_oplog.record(1, "dmlc.ingest", 4.0, children={
        "dmlc.ingest.host_prep": [1, 0.9, 0.9, 0]})] + log[1:]
    monkeypatch.setattr(_oplog, "fetch", lambda: (dense, 0))
    assert read(test_spans.ctx_of([], spans, [], ops=1),
                "setup.ingest_regroup_s") is None
    monkeypatch.setattr(_oplog, "fetch", lambda: None)
    assert read(test_spans.ctx_of([], spans, [], ops=1),
                "setup.ingest_regroup_s") is None


# -- the data rule ---------------------------------------------------------------------

def test_the_groups_are_the_data_sets_and_the_rows_the_seeds(monkeypatch):
    lens = datagen_rank.group_sizes(31_531, 3_771_125, 1_251)
    assert (lens.sum(), lens.min(), lens.max()) == (3_771_125, 1, 1_251)
    assert np.median(lens) < lens.mean() < 2 * np.median(lens)   # a tail
    a = datagen_rank.mslr_like(400, 20000, 16, SEED, max_group=300)
    monkeypatch.setattr(datagen_rank, "_THREADS", 1)
    b = datagen_rank.mslr_like(400, 20000, 16, SEED, max_group=300)
    for u, v in zip(a, b):
        assert np.array_equal(u, v)
    X, y, qid = a
    sizes = np.bincount(qid)
    assert (len(sizes), sizes.sum(), sizes.min(), sizes.max()) == \
        (400, 20000, 1, 300)
    # another seed: the same list of sizes, other rows in another order
    X2, y2, qid2 = datagen_rank.mslr_like(400, 20000, 16, 7, max_group=300)
    assert np.array_equal(np.bincount(qid2), sizes)
    assert not np.array_equal(qid2, qid) and not np.array_equal(X2, X)
    # the queries are NOT in order, and some are riffled with another
    assert (np.diff(qid) < 0).sum() > 100
    runs = 1 + np.count_nonzero(np.diff(qid))
    assert runs > 400 + 50
    # five grades near MSLR's shares; a query's offset shows in column 5
    share = np.bincount(y.astype(int), minlength=5) / len(y)
    assert np.abs(share - np.array(datagen_rank.GRADE_SHARES)).max() < 0.03
    mean5 = np.bincount(qid, weights=X[:, 5]) / sizes
    rel_q = np.bincount(qid, weights=y) / sizes
    big = sizes >= 20
    assert np.corrcoef(mean5[big], rel_q[big])[0, 1] > 0.5
    # held-out queries: another stream of the same rule
    Xh, yh, qh = datagen_rank.mslr_like(64, None, 16, SEED, stream=1,
                                        max_group=300)
    assert len(np.unique(qh)) == 64 and len(Xh) == len(yh) == len(qh)


# -- the reference ---------------------------------------------------------------------

def test_the_reference_is_the_equations_pair_by_pair():
    rng = np.random.default_rng(3)
    s = rng.normal(size=9)
    s[[2, 5]] = s[0]                                   # ties by position
    rel = rng.integers(0, 5, 9).astype(float)
    g, h = rr.query_grad_hess(s, rel)
    rank = np.empty(9, int)
    rank[np.argsort(-s, kind="stable")] = np.arange(9)
    idcg = ((2 ** np.sort(rel)[::-1] - 1) / np.log2(2 + np.arange(9))).sum()
    G, H = np.zeros(9), np.zeros(9)
    for i in range(9):
        for j in range(9):
            if rel[i] > rel[j]:
                p = 1 / (1 + np.exp(s[i] - s[j]))
                w = (abs(2 ** rel[i] - 2 ** rel[j])
                     * abs(1 / np.log2(2 + rank[i]) - 1 / np.log2(2 + rank[j]))
                     / idcg)
                G[i] -= p * w
                G[j] += p * w
                H[i] += p * (1 - p) * w
                H[j] += p * (1 - p) * w
    assert np.allclose(g, G, atol=1e-15) and np.allclose(h, np.maximum(
        H, 1e-16), atol=1e-15)
    # one document, one level: no pair
    for s1, r1 in (([0.3], [2.0]), ([0.1, 0.2, 0.3], [1.0, 1.0, 1.0])):
        g1, h1 = rr.query_grad_hess(np.array(s1), np.array(r1))
        assert (g1 == 0).all() and (h1 == 1e-16).all()
    # the loop over queries, dealt to processes or not, is the same loop
    bounds = np.array([0, 4, 9])
    gb, hb = rr.lambda_grad_hess(s, rel, bounds)
    assert np.array_equal(gb[4:], rr.query_grad_hess(s[4:], rel[4:])[0])
    assert rr.ndcg_at(np.array([3.0, 2.0, 1.0]), np.array([2.0, 1.0, 0.0]),
                      np.array([0, 3])) == 1.0
    assert rr.ndcg_at(np.array([1.0, 2.0, 3.0]), np.array([2.0, 1.0, 0.0]),
                      np.array([0, 3])) < 0.8
    assert rr.ndcg_at(np.zeros(3), np.zeros(3), np.array([0, 3])) == 1.0


def test_a_large_table_is_dealt_to_processes_and_reads_the_same(
        monkeypatch):
    rng = np.random.default_rng(4)
    lens = rng.integers(1, 60, 300)
    bounds = np.r_[0, np.cumsum(lens)]
    s = rng.normal(size=bounds[-1])
    rel = rng.integers(0, 5, bounds[-1]).astype(float)
    one = rr.lambda_grad_hess(s, rel, bounds)
    monkeypatch.setattr(rr, "_SPREAD_FROM_PAIRS", 1000)
    monkeypatch.setattr(rr, "_PROCESSES", 3)
    many = rr.lambda_grad_hess(s, rel, bounds, control="pads_first",
                               width_of=checks_rank.ladder_width)
    sound = rr.lambda_grad_hess(s, rel, bounds)
    assert np.array_equal(one[0], sound[0]) and np.array_equal(one[1],
                                                               sound[1])
    assert not np.array_equal(many[0], sound[0])


# -- the program and the controls ----------------------------------------------------

CFG = dict(shipped("configs", CONFIG), queries=500, rows=40000, features=24,
           max_group=400, n_bins=64, n_summary=512, max_depth=5)


@pytest.fixture(scope="module")
def fitted():
    from dmlc_core_tpu.models import HistGBT

    X, y, qid = datagen_rank.mslr_like(CFG["queries"], CFG["rows"],
                                       CFG["features"], SEED,
                                       max_group=CFG["max_group"])
    model = HistGBT(n_trees=12, max_depth=CFG["max_depth"],
                    n_bins=CFG["n_bins"],
                    learning_rate=CFG["learning_rate"],
                    min_child_weight=CFG["min_child_weight"],
                    objective="rank:ndcg")
    handle = model.make_device_data(X, y, qid=qid)
    model.fit_device(handle)
    trees = [{k: np.asarray(v) for k, v in t.items()} for t in model.trees]
    order, bounds = rr.query_bounds(qid)
    return (X, y, qid, order, bounds, trees, np.asarray(handle["bins_t"]),
            np.asarray(model.cuts), model)


def test_the_handle_holds_every_document_once_in_query_order(fitted):
    X, y, qid, order, bounds, trees, bins_t, cuts, model = fitted
    assert bins_t.shape == (CFG["features"], CFG["rows"])
    assert checks.bins_mismatches(X[order[:4096]], bins_t[:, :4096],
                                  cuts) == 0
    plan = model.round_plan
    assert plan["rank_pairs"] == int((np.diff(bounds) ** 2).sum())
    assert plan["rank_pairs"] < plan["rank_pair_slots"] < \
        2.25 * plan["rank_pairs"]
    assert sum(q for _w, q in plan["rank_buckets"]) >= CFG["queries"]
    # predict answers in the caller's row order
    margin = ref.ensemble_margin(X[:2048], cuts, trees, CFG["base_score"])
    assert np.abs(margin - model.predict(X[:2048],
                                         output_margin=True)).max() < 1e-5


def test_the_program_keeps_the_limits_that_no_size_moves(fitted):
    X, y, qid, order, bounds, trees, bins_t, cuts, _model = fitted
    got = checks_rank.boost_tree_numbers(bins_t, y[order], bounds, trees,
                                         CFG)
    assert set(got) | {"rounds_share", "rows_share", "bins_mismatches",
                       "ops_trees_differ", "train_ndcg10",
                       "heldout_ndcg10"} == \
        set(shipped("traffic", MIX)["limits"])
    # the CPU feeds float32 gradients to a float32 histogram: every gap
    # is rounding, far inside the chip's limits
    for name in ("tree0.root_gain_gap", "tree0.reported_gain_gap",
                 "tree0.leaf_gap", "tree1.leaf_gap"):
        assert got[name] <= min(limit(name), 1e-4), (name, got)


@pytest.mark.parametrize("control, fails", [
    ("truncate128", "tree0.leaf_gap"), ("pairwise", "tree0.root_gain_gap"),
    ("reverse_ties", "tree0.leaf_gap"), ("pads_first", "tree0.leaf_gap"),
    ("bfloat16_pairs", "tree0.leaf_gap"), ("bfloat16", "tree0.leaf_gap"),
    ("float8", "tree1.leaf_gap")])
def test_each_control_leaves_a_limit(fitted, control, fails):
    X, y, qid, order, bounds, trees, bins_t, cuts, _model = fitted
    rel = y[order]
    got = checks_rank.boost_tree_numbers(
        bins_t, rel, bounds, checks_rank.control_trees(
            bins_t, rel, bounds, trees, CFG, control), CFG)
    assert got[fails] > limit(fails), got


def test_a_fit_stopped_early_shows_in_what_the_ensemble_learns(fitted):
    X, y, qid, order, bounds, trees, bins_t, cuts, _model = fitted
    Xh, yh, qh = datagen_rank.mslr_like(128, None, CFG["features"], SEED,
                                        stream=1,
                                        max_group=CFG["max_group"])
    oh, bh = rr.query_bounds(qh)
    m = int(bounds[128])

    def learn(some):
        return checks_rank.learning_numbers(
            X[order[:m]], y[order[:m]], bounds[bounds <= m], Xh[oh], yh[oh],
            bh, cuts, some, CFG)

    whole, short = learn(trees), learn(trees[:2])
    assert whole["train_ndcg10"] > short["train_ndcg10"]
    assert whole["heldout_ndcg10"] > 0.6
    assert checks.trees_differ(trees, trees) == 0
