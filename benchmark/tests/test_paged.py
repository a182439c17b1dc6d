"""What ISSUE 51 adds to the benchmark, on the CPU at toy size: the paged
one-hot configuration (Allstate at the XGBoost paper's settings), its
data rule (CSR blocks, never dense), the reference of a SKETCHED cut (its
rank, not its value), two operations that stage through ``DiskRowIter``
pages, two mixes whose limits are the cells' own, and five readers.  The
shipped files load and run in a scratch root as files only (their sizes
cut); each mix's limits name every number its operation's check
produces; each control leaves a limit; the readers read a synthetic trace
and are silent on a program without their span, scope or record.

``BENCHMARK.json`` is append-only: membership is asserted with ``<=``.
"""

import json
import os
import shutil

import numpy as np
import pytest

import test_spans
import util
from benchmark import (checks, checks_paged, datagen_onehot as D, harness,
                       reference as ref, reference_paged as refp, xplane)
from benchmark.metrics import _oplog, _spans

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
SEED = 2**31 + 51
CONFIG = "allstate-1m25-d8"
INGEST, BOOST = CONFIG + ".ingest-paged", CONFIG + ".boost-r5-paged"
NEW_READERS = ["ingest.iter.page_wait_s", "ingest.iter.densify_s",
               "ingest.iter.host_scan_copy_s", "ingest.iter.sketch_device_s",
               "setup.ingest_pages_s"]
#: nineteen fields as the table has them, two of them wide
LEVELS = [8, 90, 140, 6, 3, 4, 3, 3, 3, 3, 3, 3, 3, 2, 3, 3, 3, 4, 3]
F = D.NUMERIC + sum(LEVELS)


def shipped(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def limit(mix, name):
    lim = shipped("traffic", mix)["limits"][name]
    return lim["limit"] if isinstance(lim, dict) else lim


def bench_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# -- the shipped files -------------------------------------------------------------

def test_the_configuration_is_the_papers_cut_on_rows_alone():
    cfg, deep = shipped("configs", CONFIG), shipped("configs",
                                                    "higgs-24m-d8")
    assert (cfg["rows"], cfg["published_rows"], cfg["features"],
            cfg["numeric_features"], cfg["heldout_rows"]) == \
        (1_250_000, 10_000_000, 4227, 12, 125_000)
    assert cfg["features"] == D.FEATURES == D.NUMERIC + sum(D.LEVELS)
    assert sorted(D.LEVELS)[-2] > 1000 and len(D.FIELDS) == 19
    assert (cfg["max_depth"], cfg["learning_rate"], cfg["reg_lambda"],
            cfg["n_bins"], cfg["min_child_weight"], cfg["base_score"]) == \
        (8, 0.1, 1.0, 256, 1.0, 0.0)
    assert cfg["n_summary"] == 8 * cfg["n_bins"]
    assert 16_384 <= cfg["slab_rows"] <= 131_072
    assert cfg["reduced"] == ["rows"] and cfg["chips"] == 1
    assert cfg["guarantees"].startswith(deep["guarantees"])
    for clause in ("rank error at most eps", "every indicator keeps a cut",
                   "an absent entry is 0.0"):
        assert clause in cfg["guarantees"]
    assert cfg["precision"] == deep["precision"]
    assert len(cfg["source"]) <= 200 and "Table 2" in cfg["source"]
    assert "8 data-parallel workers" in cfg["deployment"]
    assert any("chip" in a and "GiB" in a for a in cfg["assumed"])
    assert any("absent" in a and "direction" in a for a in cfg["assumed"])
    bench = bench_json()
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry["source"] == cfg["source"] and entry["reduced"] == ["rows"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert len({c["source"] for c in bench["configs"]}) == \
        len(bench["configs"])


def test_the_boost_cell_is_an_entry_and_a_name_in_lists():
    cell, e2e = BOOST, "boost_rounds_per_s"
    emits = {"hist.time_share", "hist.mxu_share", "round.hist_ms",
             "round.hist_ms.deepest", "round.nonhist_ms", "round.fblock_ms",
             "round.nblock_ms", "setup.ingest_s", "setup.ingest_stream_s",
             "setup.compile_s", "setup.compile_wait_s", "setup.fit_s",
             "setup.ingest_pages_s"}
    bench = bench_json()
    (entry,) = [w for w in bench["workloads"] if w["name"] == cell]
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        (CONFIG, "boost-r5-paged", 1)
    assert len(entry["why"]) <= 200
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if cell in m.get("workloads", ())}
    assert {e2e} | emits <= listed
    assert [m["name"] for m in harness.metrics_of(bench, "end_to_end",
                                                  cell)] == [e2e, "setup_s"]
    for m in harness.metrics_of(bench, "per_layer", cell):
        harness.find_file(ROOT, bench["paths"], "metrics", m["name"] + ".py")
        assert m["moves"] in (e2e, "setup_s"), m
    # the spans this path never opens: the host_prep of a whole matrix
    assert "setup.ingest_host_prep_s" not in listed


def test_the_ingest_cell_ships_as_files_without_an_entry():
    """Five seeds of ``.ingest-paged`` spread by 2.8% where a new cell
    may spread by 1.25% (PERF.md section 7): its operation, mix, limits
    and readers ship and are held to the tests here; the entry is a data
    PR's once the host's copies are steadier."""
    bench = bench_json()
    assert INGEST not in [w["name"] for w in bench["workloads"]]
    assert not any(INGEST in m.get("workloads", ())
                   for m in bench["end_to_end"] + bench["per_layer"])
    entries = {m["name"] for m in bench["per_layer"]}
    assert not entries & set(NEW_READERS[:4])
    for reader in NEW_READERS:
        harness.find_file(ROOT, bench["paths"], "metrics", reader + ".py")
    harness.find_file(ROOT, bench["paths"], "ops", "ingest_paged.py")
    assert shipped("traffic", "ingest-paged")["op"] == "ingest_paged"


def test_the_new_cell_follows_the_accepted_entries_and_nothing_else_moved():
    bench = bench_json()
    cells = [w["name"] for w in bench["workloads"]]
    assert cells[10] == BOOST and len(cells[:10]) == 10
    assert [c["name"] for c in bench["configs"]][7] == CONFIG
    layers = [m["name"] for m in bench["per_layer"]]
    assert layers.index("setup.ingest_pages_s") > layers.index(
        "round.update_ms")
    assert bench["run_seconds"] == 20
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 2


@pytest.mark.parametrize("traffic, op, e2e", [
    ("ingest-paged", "ingest_paged", "ingest_rows_per_s"),
    ("boost-r5-paged", "boost_paged", "boost_rounds_per_s")])
def test_a_mix_says_where_each_limit_comes_from(traffic, op, e2e):
    bench = bench_json()
    mix = shipped("traffic", traffic)
    assert mix["op"] == op and mix["end_to_end"] == {e2e: {"kind": "rate"}}
    assert set(mix["limits"]) - {"rounds_share", "rows_share"} <= \
        set(mix["limits_from"])
    assert "PR 51" in mix["limits_from"]["readings"]
    harness.find_file(ROOT, bench["paths"], "ops", op + ".py")
    if traffic == "boost-r5-paged":
        assert (mix["params"]["n_trees"], mix["params"]["warm_trees"]) == \
            (5, 5)


def test_the_boost_window_is_the_accepted_operation():
    import inspect

    def fn(name, f):
        mod = harness.load_module(os.path.join(BENCH, "ops", name + ".py"))
        return inspect.getsource(getattr(mod, f))

    assert fn("boost_paged", "op") == fn("boost", "op")
    for f in ("op", "_one"):
        assert "make_device_data" not in fn("ingest_paged", f)
        assert "cuts" not in fn("ingest_paged", f)


def test_the_boost_cell_holds_the_cut_guarantee_alone():
    """No ingest cell runs, so the cell that does compares the sketched
    cuts' rank error itself, at the ingest mix's limit; the controls'
    script puts a reading through ``checks.apply_limits`` against the
    shipped mix, so a thinner sketch's reading leaves that limit in the
    boost cell and a sound one does not."""
    import inspect

    boost, ingest = (shipped("traffic", m) for m in ("boost-r5-paged",
                                                     "ingest-paged"))
    assert boost["limits"]["cuts_rank_error"] == \
        ingest["limits"]["cuts_rank_error"] == 0.0025
    assert "warm_trees is 5 where ISSUE 51 names 2" in boost["what"]
    check = inspect.getsource(harness.load_module(
        os.path.join(BENCH, "ops", "boost_paged.py")).check)
    assert "cut_numbers" in check and "not compared" not in check
    on_chip = harness.load_module(os.path.join(HERE, "paged_on_chip.py"))
    sound = {"cuts_rank_error": 0.000558, "indicator_cuts_missing": 0}
    for mix in (boost, ingest):
        assert on_chip.broken(mix, sound) == []
        for reading in (0.00508, 0.0242):      # first slab; 64 points
            assert on_chip.broken(mix, dict(sound, cuts_rank_error=reading)) \
                == ["cuts_rank_error"]
    with pytest.raises(KeyError):
        on_chip.broken(boost, {"pages_replayed": 2.0})


# -- in a scratch root, as files only ------------------------------------------------

def run(root, cell, trace=False):
    lines = []
    out = harness.run_cell(root, cell, SEED, 0.3, trace, require_chip=False,
                           say=lines.append)
    return out, lines


@pytest.fixture(scope="module")
def scratch(tmp_path_factory):
    root = util.make_root(tmp_path_factory.mktemp("paged"))
    base = os.path.join(root, "bench_data")
    cfg = dict(shipped("configs", CONFIG), rows=6000, heldout_rows=2048,
               slab_rows=1024, n_bins=32, n_summary=256, max_depth=4,
               field_levels=LEVELS, features=F)
    json.dump(cfg, open(f"{base}/configs/allstate.json", "w"))
    mixes = {}
    for name in ("ingest-paged", "boost-r5-paged"):
        mix = shipped("traffic", name)
        if name == "ingest-paged":
            mix["params"] = dict(mix["params"], check_bin_rows=512)
            # six slabs of 1,024 rows through a 256-point summary: the
            # toy's own eps, the shipped file's limits for the rest
            mix["limits"] = dict(mix["limits"], cuts_rank_error=refp.
                                 sketch_eps(256, 6))
        else:
            mix["params"] = dict(mix["params"], n_trees=3, warm_trees=3,
                                 check_bin_rows=256, check_heldout_rows=2048,
                                 check_train_rows=2048)
            mix["limits"] = dict(
                mix["limits"], train_logloss=0.69,
                heldout_auc={"limit": 0.3, "passes": "at_least"},
                cuts_rank_error=refp.sketch_eps(256, 6))
        mixes[name] = mix
        json.dump(mix, open(f"{base}/traffic/{name}.json", "w"))
    for reader in NEW_READERS:
        shutil.copy(os.path.join(BENCH, "metrics", reader + ".py"),
                    f"{base}/metrics/{reader}.py")
    bench = json.load(open(f"{root}/BENCHMARK.json"))
    bench["configs"].append({"name": "allstate", "source": cfg["source"],
                             "file": "bench_data/configs/allstate.json",
                             "reduced": ["rows"], "why": "self-test"})
    for name, e2e in (("ingest-paged", "ingest_rows_per_s"),
                      ("boost-r5-paged", "boost_rounds_per_s")):
        bench["workloads"].append({"name": "allstate." + name,
                                   "config": "allstate", "traffic": name,
                                   "chips": 1, "why": "self-test"})
        for m in bench["end_to_end"]:
            if m["name"] == e2e:
                m["workloads"].append("allstate." + name)
    # the readers' entries: set-up's as shipped, the ingest cell's as
    # the PR that adds the cell will write them
    (pages,) = [m for m in bench_json()["per_layer"]
                if m["name"] == "setup.ingest_pages_s"]
    assert pages["workloads"] == [BOOST]
    bench["per_layer"].append(dict(pages, workloads=[
        "allstate.ingest-paged", "allstate.boost-r5-paged"]))
    for reader, source in zip(NEW_READERS[:4], ["program_span"] * 3
                              + ["device_trace"]):
        bench["per_layer"].append({
            "name": reader, "unit": "s/op", "better": "lower",
            "source": source, "layer": "ingest",
            "moves": "ingest_rows_per_s",
            "workloads": ["allstate.ingest-paged"]})
    json.dump(bench, open(f"{root}/BENCHMARK.json", "w"))
    return root, mixes


@pytest.mark.parametrize("mix, e2e", [("ingest-paged", "ingest_rows_per_s"),
                                      ("boost-r5-paged",
                                       "boost_rounds_per_s")])
def test_new_files_run_in_a_scratch_root(scratch, mix, e2e):
    root, mixes = scratch
    out, lines = run(root, "allstate." + mix)
    assert out["correct"] is True, lines
    assert out["metrics"][e2e]["value"] > 0
    assert set(out["metrics"]) == {e2e, "setup_s"}
    assert set(out["compared"]) == set(mixes[mix]["limits"]) | {
        "window.compiles", "ops.failed"}
    if mix == "ingest-paged":
        assert out["compared"]["pages_replayed"]["value"] == 2.0
    # the page cache lies in the checkout's ignored output and is gone
    assert os.listdir(os.path.join(root, "benchmark", ".out", "pages")) == []


def test_a_traced_run_without_the_marks_leaves_the_new_metrics_out(
        scratch, monkeypatch):
    root, _ = scratch
    planes = {"/device:TPU:0": {xplane.OPS_LINE: [("fusion.2", 1.0, 1.5)],
                                xplane.MODULES_LINE: [("jit_a(1)", 1.0,
                                                       1.5)]},
              "/host:CPU": {"main": [("bench.window", 0.0, 2.0),
                                     ("bench.op", 0.5, 2.0)]}}
    monkeypatch.setattr(xplane, "load", lambda path: planes)
    monkeypatch.setattr(_spans, "load", lambda path: _spans.Marks(
        [], [[("", 1.0, 1.5)]]))
    for mix in ("ingest-paged", "boost-r5-paged"):
        out, lines = run(root, "allstate." + mix, trace=True)
        assert not set(NEW_READERS) & set(out["metrics"]), lines
        assert "compile.cache_misses" in out["metrics"]


# -- the readers ---------------------------------------------------------------------

read = test_spans.read


def test_the_iterator_readers_on_a_synthetic_ingest(monkeypatch):
    # two operations: page waits 0.5 + 0.25 s, densify 1 s, copy 0.5 and
    # scan 0.25 s each; the sketch 2 s of device time each beside
    # 0.25 s of binning
    ops = [("dmlc.sketch.add", 1.0, 2.5), ("dmlc.sketch.merge", 2.5, 2.75),
           ("dmlc.sketch.finalize", 2.75, 3.0), ("dmlc.bin", 3.25, 3.5),
           ("dmlc.sketch.add", 6.0, 8.0), ("dmlc.bin", 8.25, 8.5)]
    spans = []
    for op, t in ((1, 0.0), (2, 5.0)):
        spans += [("dmlc.ingest", t, t + 4.0, op),
                  ("dmlc.ingest.iter.sketch_pass", t, t + 3.0, op),
                  ("dmlc.ingest.iter.page_wait", t, t + 0.5, op),
                  ("dmlc.ingest.iter.page_wait", t + 3.0, t + 3.25, op),
                  ("dmlc.ingest.iter.densify", t + 0.5, t + 1.5, op),
                  ("dmlc.ingest.iter.copy", t + 1.5, t + 2.0, op),
                  ("dmlc.ingest.iter.nan_scan", t + 2.0, t + 2.25, op)]
    ctx = test_spans.ctx_of(ops, spans, [], ops=2)
    assert read(ctx, "ingest.iter.page_wait_s") == pytest.approx(0.75)
    assert read(ctx, "ingest.iter.densify_s") == pytest.approx(1.0)
    assert read(ctx, "ingest.iter.host_scan_copy_s") == pytest.approx(0.75)
    assert read(ctx, "ingest.iter.sketch_device_s") == pytest.approx(2.0)
    assert read(ctx, "ingest.bin_device_s") == pytest.approx(0.25)
    # the in-memory ingest: neither the spans nor the scope
    dense = test_spans.ctx_of(
        [("dmlc.cuts", 1.0, 3.0)],
        [("dmlc.ingest", 0.0, 4.0, 1),
         ("dmlc.ingest.stream", 1.0, 2.0, 1)], [], ops=1)
    for name in NEW_READERS[:4]:
        assert read(dense, name) is None
    # set-up's page build, from the program's record
    log = [{"op": 1, "name": "dmlc.pages.build", "start": 0.0, "end": 2.5,
            "counts": {"pages": 7}, "children": {}, "compile": {}},
           {"op": 2, "name": "dmlc.ingest", "start": 3.0, "end": 4.0,
            "counts": {}, "children": {}, "compile": {}}]
    monkeypatch.setattr(_oplog, "fetch", lambda: (log, 0))
    ctx = test_spans.ctx_of([], [("dmlc.ingest", 5.0, 9.0, 3)], [], ops=1)
    ctx.say = lambda s: None
    assert read(ctx, "setup.ingest_pages_s") == pytest.approx(2.5)
    monkeypatch.setattr(_oplog, "fetch", lambda: (log[1:], 0))
    ctx = test_spans.ctx_of([], [("dmlc.ingest", 5.0, 9.0, 3)], [], ops=1)
    ctx.say = lambda s: None
    assert read(ctx, "setup.ingest_pages_s") is None


# -- the data rule ---------------------------------------------------------------------

def test_the_rows_are_the_seeds_in_blocks_never_dense():
    a = list(D.allstate_like(70000, SEED, levels=LEVELS))
    b = list(D.allstate_like(70000, SEED, levels=LEVELS))
    assert [len(x[3]) for x in a] == [D.BLOCK_ROWS, 70000 - D.BLOCK_ROWS]
    for x, z in zip(a, b):
        for u, v in zip(x, z):
            assert u.dtype == v.dtype and np.array_equal(u, v)
    # the first block does not depend on how many follow
    first = next(D.allstate_like(D.BLOCK_ROWS, SEED, levels=LEVELS))
    for u, v in zip(a[0], first):
        assert np.array_equal(u, v)
    offset, index, value, y = a[0]
    per_row = np.diff(offset)
    assert per_row.min() >= D.NUMERIC and per_row.max() <= D.NUMERIC + 19
    assert abs(per_row.mean() - (D.NUMERIC + 19 * 0.97)) < 0.05
    # a row's indices ascend: twelve numerics, then one level a field
    rows = np.repeat(np.arange(len(y)), per_row)
    assert np.all((np.diff(index) > 0) | (np.diff(rows) > 0))
    assert np.all(value[index >= D.NUMERIC] == 1.0)
    bounds = D.field_bounds(LEVELS)
    field = np.searchsorted(bounds, index[index >= D.NUMERIC], "right")
    assert np.all(np.diff(field)[np.diff(rows[index >= D.NUMERIC]) == 0] > 0)
    # another stream, a large seed; the full table's columns
    other = next(D.allstate_like(100, 2**31 + 5, stream=1, levels=LEVELS))
    same = next(D.allstate_like(100, 2**31 + 5, stream=0, levels=LEVELS))
    assert not np.array_equal(other[2], same[2])
    full = next(D.allstate_like(1000, SEED))
    assert full[1].max() < D.FEATURES == 4227
    with pytest.raises(ValueError):
        D.levels_of({"features": 300, "field_levels": LEVELS})
    assert D.levels_of({"features": 4227}) == D.LEVELS


def test_rare_levels_and_the_rare_label():
    blocks = list(D.allstate_like(200_000, SEED))
    y = np.concatenate([b[3] for b in blocks])
    assert 0.005 < y.mean() < 0.010
    count = np.zeros(D.FEATURES, np.int64)
    for _o, index, _v, _y in blocks:
        count += np.bincount(index, minlength=D.FEATURES)
    assert np.all(count[:D.NUMERIC] == len(y))
    ind = count[D.NUMERIC:]
    assert (ind < 10).sum() > 500 and ind.max() > 0.3 * len(y)
    occupied = checks_paged.occupied_indicators(blocks, len(y), D.FEATURES)
    assert len(occupied) == np.count_nonzero(ind)


# -- the reference and the controls ------------------------------------------------------

def test_a_cut_is_judged_by_its_rank():
    rng = np.random.default_rng(0)
    col = np.sort(rng.normal(size=100_000))
    exact = np.quantile(col, np.arange(1, 256) / 256)
    assert refp.cut_rank_errors(col, exact).max() < 2e-5
    # a cut one percent of the rows off its aim reads 0.01, wherever
    off = exact.copy()
    off[100] = col[int(100_000 * (101 / 256 + 0.01))]
    assert refp.cut_rank_errors(col, off).max() == pytest.approx(0.01,
                                                                 abs=1e-4)
    # a cut ON a run of equal values has its aim inside the run: 0; the
    # guard's bumped copies of it lie past the run and read their
    # distance to it (why the number is taken on continuous columns)
    ties = np.sort(np.r_[np.zeros(90_000), np.ones(10_000)])
    err = refp.cut_rank_errors(ties, np.array([0.0, 1e-6, 1.0]))
    assert err[0] == 0 and err[1:] == pytest.approx([0.9 - 0.5, 0.9 - 0.75])
    assert refp.sketch_eps(2048, 20) == pytest.approx(5 / 2047)
    assert refp.sketch_eps(2048, 1) == pytest.approx(4 / 2047)
    assert refp.sketch_eps(64, 20) > 0.07


def test_densify_puts_zero_where_the_block_has_no_entry():
    offset, index, value, _y = next(D.allstate_like(64, SEED, levels=LEVELS))
    X = refp.densify(offset, index, value, F)
    assert X.shape == (64, F) and X.dtype == np.float64
    assert np.count_nonzero(X) == len(index) - np.count_nonzero(value == 0)
    assert np.array_equal(X[3, index[offset[3]:offset[4]]],
                          value[offset[3]:offset[4]].astype(np.float64))
    assert np.array_equal(refp.densify(offset, index, value, F, 10, 20),
                          X[10:20])


@pytest.fixture(scope="module")
def fitted():
    """The program on the CPU at 20,000 x 302 through the pages, depth 6."""
    from benchmark import system_paged
    from dmlc_core_tpu.models import HistGBT

    cfg = dict(util.TINY_CONFIG, rows=20000, features=F, n_bins=64,
               n_summary=512, max_depth=6, learning_rate=0.1)
    blocks = list(D.allstate_like(cfg["rows"], SEED, levels=LEVELS))
    pages = system_paged.build_pages(
        blocks, os.path.join(ROOT, "benchmark", ".out", "pages",
                             "selftest.cache"))
    model = HistGBT(n_trees=5, max_depth=6, n_bins=64, learning_rate=0.1,
                    objective="binary:logistic")
    handle = system_paged.ingest_paged(model, pages, F, 2048)
    model.fit_device(handle)
    thin = system_paged.sketch_cuts(pages, F, 2048, 64, 16)
    first = system_paged.sketch_cuts(pages, F, 2048, 64, 512, slabs=1)
    pages.drop()
    trees = [{k: np.asarray(v) for k, v in t.items()} for t in model.trees]
    y = np.concatenate([b[3] for b in blocks])
    return (cfg, blocks, y, trees, np.asarray(handle["bins_t"])[:, :len(y)],
            np.asarray(model.cuts), thin, first)


def test_each_ingest_control_leaves_a_limit(fitted):
    cfg, blocks, y, trees, bins_t, cuts, thin, first = fitted
    n, ids = len(y), list(range(D.NUMERIC))
    columns = checks_paged.numeric_columns(blocks, ids)
    occupied = checks_paged.occupied_indicators(blocks, n, F)
    eps = refp.sketch_eps(512, -(-n // 2048))
    sound = checks_paged.cut_numbers(columns, ids, occupied, cuts)
    assert sound["cuts_rank_error"] <= eps
    assert sound["indicator_cuts_missing"] == \
        limit("ingest-paged", "indicator_cuts_missing") == 0
    for control in (thin, first):
        got = checks_paged.cut_numbers(columns, ids, occupied, control)
        assert got["cuts_rank_error"] > eps
        assert got["indicator_cuts_missing"] == 0     # the guard's doing
    lost = cuts.copy()
    lost[occupied[7]] = 2.0 + np.arange(cuts.shape[1])
    assert refp.unsplit_indicators(lost, occupied) == 1
    block = checks_paged.dense_rows(blocks, 100, 2048, F)
    assert checks_paged.bins_mismatches(block, bins_t[:, 100:2148],
                                        cuts) == 0
    assert checks_paged.bins_mismatches(
        block, ref.bin_rows(block, cuts, "bfloat16").T, cuts) > \
        limit("ingest-paged", "bins_mismatches")


@pytest.mark.parametrize("control, fails", [
    ("bfloat16", "tree0.leaf_gap"), ("float8", "tree1.leaf_gap_by_rows"),
    ("column_plus_1", "tree0.root_gain_gap")])
def test_each_tree_control_leaves_a_limit(fitted, control, fails):
    cfg, _blocks, y, trees, bins_t, _cuts, _thin, _first = fitted
    sound = checks_paged.boost_tree_numbers(bins_t, y, trees, cfg)
    assert set(sound) | {"rounds_share", "bins_mismatches",
                         "indicator_cuts_missing", "cuts_rank_error",
                         "ops_trees_differ",
                         "score_gap", "train_logloss", "heldout_auc"} == \
        set(shipped("traffic", "boost-r5-paged")["limits"])
    for name in ("tree0.root_gain_gap", "tree0.leaf_gap",
                 "tree1.leaf_gap_by_rows"):
        assert sound[name] <= limit("boost-r5-paged", name), (name, sound)
    if control == "column_plus_1":
        put = [dict(t, feat=(t["feat"] + 1) % F) for t in trees]
    else:
        put = checks.control_trees(bins_t, y, trees, cfg, control)
    got = checks_paged.boost_tree_numbers(bins_t, y, put, cfg)
    assert got[fails] > limit("boost-r5-paged", fails), got
