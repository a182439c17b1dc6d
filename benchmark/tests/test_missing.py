"""What ISSUE 42 adds to the benchmark, on the CPU at toy size: the
missing-value configuration (Bosch at gbm-bench's settings), its data
rule and its plain reference, two operations with the accepted windows,
two mixes whose limits are the cells' own, and three readers.  The
shipped files load and run in a scratch root as files only (their sizes
cut); each mix's limits name every number its operation's check
produces; the program keeps every limit that does not depend on the size
and each control leaves one; the readers read a synthetic trace and are
silent on a program without their span or scope.

``BENCHMARK.json`` holds the boost cell alone: the ingest cell's
``ingest_rows_per_s`` spread by 1.9% over the builder's runs where a new
cell may spread by 1.25% (PERF.md section 7), so its operation, mix and
two readers ship as files that no entry names yet, held to the same
tests here, and the cell is a data entry for the PR that steadies it.

Unlike ``test_wide.py`` / ``test_deep.py``, nothing here asserts that a
list of ``BENCHMARK.json`` EQUALS a set: the file is append-only, a later
PR may put these cells on more lists (as PR 39 did to theirs, which is
why those two files fail since), so membership is asserted with ``<=``.
"""

import json
import os
import shutil

import numpy as np
import pytest

import test_spans
import util
from benchmark import (checks, checks_missing, datagen_missing, harness,
                       reference_missing as ref, xplane)
from benchmark.metrics import _spans

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
SEED = 2**31 + 42
CONFIG = "bosch-1m-d8"
BOOST = CONFIG + ".boost-r25-nan"
NEW_READERS = ["ingest.idle_s.nan_scan", "ingest.cuts_finite_device_s",
               "round.split_ms"]


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

def test_the_configuration_is_the_sources_uncut():
    cfg, deep = shipped("configs", CONFIG), shipped("configs",
                                                    "higgs-24m-d8")
    assert (cfg["rows"], cfg["features"], cfg["heldout_rows"]) == \
        (1_183_747, 968, 200_000)
    assert (cfg["max_depth"], cfg["learning_rate"], cfg["reg_lambda"],
            cfg["n_bins"], cfg["min_child_weight"], cfg["base_score"]) == \
        (8, 0.1, 1.0, 256, 1.0, 0.0)
    assert (cfg["missing_share"], cfg["positive_share"]) == (0.81, 0.0058)
    assert cfg["reduced"] == [] and cfg["chips"] == 1
    # 8 summary points a VALUE bin, and one bin is reserved
    assert cfg["n_summary"] == 8 * (cfg["n_bins"] - 1)
    assert cfg["guarantees"].startswith(deep["guarantees"])
    assert "NaN is missing" in cfg["guarantees"]
    assert cfg["precision"] == deep["precision"]
    assert len(cfg["source"]) <= 200 and "bosch" in cfg["source"]
    assert any("chip" in a and "GiB" in a for a in cfg["assumed"])
    bench = bench_json()
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry["source"] == cfg["source"] and entry["reduced"] == []
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert len({c["source"] for c in bench["configs"]}) == \
        len(bench["configs"])
    assert len({c["file"] for c in bench["configs"]}) == \
        len(bench["configs"])


def test_the_new_cell_is_an_entry_and_a_name_in_lists():
    cell, e2e = BOOST, "boost_rounds_per_s"
    emits = {"round.split_ms", "round.hist_ms", "round.hist_ms.deepest",
             "round.nonhist_ms", "round.fblock_ms", "round.nblock_ms",
             "hist.time_share", "setup.fit_s", "setup.ingest_s"}
    bench = bench_json()
    (entry,) = [w for w in bench["workloads"] if w["name"] == cell]
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        (CONFIG, "boost-r25-nan", 1)
    assert len(entry["why"]) <= 200
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if cell in m.get("workloads", ())}
    assert {e2e} | emits <= listed
    # what the cell reports end to end: its rate and set-up
    assert [m["name"] for m in harness.metrics_of(bench, "end_to_end",
                                                  cell)] == [e2e, "setup_s"]
    # every metric the cell is listed on has a reader file, and moves an
    # end-to-end metric the cell reports
    for m in harness.metrics_of(bench, "per_layer", cell):
        harness.find_file(ROOT, bench["paths"], "metrics", m["name"] + ".py")
        assert m["moves"] in (e2e, "setup_s"), m
    # not on the share of the MXU peak: its flops are counted for a
    # matrix without the reserved bin's column of every node
    assert "hist.mxu_share" not in listed


@pytest.mark.parametrize("traffic, op, e2e", [
    ("ingest-nan", "ingest_nan", "ingest_rows_per_s"),
    ("boost-r25-nan", "boost_nan", "boost_rounds_per_s")])
def test_a_mix_says_where_each_limit_comes_from(traffic, op, e2e):
    bench = bench_json()
    mix = shipped("traffic", traffic)
    assert mix["op"] == op and mix["end_to_end"] == {e2e: {"kind": "rate"}}
    assert set(mix["limits"]) - {"rounds_share", "rows_share"} <= \
        set(mix["limits_from"])
    assert "PR 42" in mix["limits_from"]["readings"]
    harness.find_file(ROOT, bench["paths"], "ops", op + ".py")
    for reader in NEW_READERS:
        harness.find_file(ROOT, bench["paths"], "metrics", reader + ".py")


def test_the_windows_are_the_accepted_operations():
    """``ops/ingest_nan.py`` and ``ops/boost_nan.py`` are ``ingest.py``
    and ``boost.py`` but for where the rows come from and what the check
    compares: ``op`` (the timed part) and ``finish`` are the same source,
    line for line."""
    import inspect

    def fn(name, f):
        mod = harness.load_module(os.path.join(BENCH, "ops", name + ".py"))
        return inspect.getsource(getattr(mod, f))

    for f in ("op", "finish", "_one"):
        assert fn("ingest_nan", f) == fn("ingest", f)
    assert fn("boost_nan", "op") == fn("boost", "op")
    # set-up differs by the data rule alone
    assert fn("boost_nan", "setup").replace(
        "datagen_missing.bosch_like(int(ctx.config[\"rows\"]),\n"
        "                                      "
        "int(ctx.config[\"features\"]),\n"
        "                                      ctx.seed, stream=0)",
        "system.training_rows(ctx)") == fn("boost", "setup").replace(
        "    # a user's repeated fit with fewer rounds: same program, a "
        "quarter of\n    # the set-up\n", "")


def test_the_new_cell_follows_the_accepted_entries_and_nothing_else_moved():
    bench = bench_json()
    cells = [w["name"] for w in bench["workloads"]]
    assert cells[:7] == [
        "higgs-24m-d6.boost", "higgs-24m-d6.ingest", "higgs-24m-d6.score",
        "higgs-d6-dp4.boost", "epsilon-400k-d6.boost-r25",
        "higgs-24m-d8.boost-r25-eta01", BOOST]
    assert [c["name"] for c in bench["configs"]][4] == CONFIG
    layers = [m["name"] for m in bench["per_layer"]]
    assert layers.index("round.split_ms") > layers.index(
        "score.idle_ms.fetch_copy")
    assert bench["run_seconds"] == 20


# -- in a scratch root, as files only ------------------------------------------------

def run(root, cell, trace=False):
    lines = []
    out = harness.run_cell(root, cell, SEED, 0.3, trace, require_chip=False,
                           say=lines.append)
    return out, lines


@pytest.fixture(scope="module")
def scratch(tmp_path_factory):
    """The shipped configuration and mixes beside the scratch root's own
    files with their SIZES cut to a test's, the shipped readers, and
    entries in its BENCHMARK.json.  The operations are found beside the
    harness, as a checkout's own ``paths`` find them."""
    root = util.make_root(tmp_path_factory.mktemp("missing"))
    base = os.path.join(root, "bench_data")
    cfg = dict(shipped("configs", CONFIG), rows=20000, features=64,
               heldout_rows=4096, n_bins=32, n_summary=248, max_depth=4)
    json.dump(cfg, open(f"{base}/configs/bosch.json", "w"))
    mixes = {}
    for name in ("ingest-nan", "boost-r25-nan"):
        mix = shipped("traffic", name)
        if name == "ingest-nan":
            mix["params"] = dict(mix["params"], check_features=4,
                                 check_bin_rows=2048)
        else:
            mix["params"] = dict(mix["params"], n_trees=3, warm_trees=3,
                                 check_bin_rows=256, check_heldout_rows=4096,
                                 check_train_rows=4096)
            # three rounds at eta 0.1 on 20,000 rows learn little, and
            # the CPU's sums are sequential: the toy's own limits for
            # what depends on the size, the shipped file's for the rest
            mix["limits"] = dict(
                mix["limits"], train_logloss=0.69,
                heldout_auc={"limit": 0.6, "passes": "at_least"},
                **{"tree0.reported_gain_gap": 1e-3,
                   "tree1.leaf_gap_by_rows": 1e-2})
        mixes[name] = mix
        json.dump(mix, open(f"{base}/traffic/{name}.json", "w"))
    for reader in NEW_READERS:
        shutil.copy(os.path.join(BENCH, "metrics", reader + ".py"),
                    f"{base}/metrics/{reader}.py")
    bench = json.load(open(f"{root}/BENCHMARK.json"))
    bench["configs"].append({"name": "bosch", "source": cfg["source"],
                             "file": "bench_data/configs/bosch.json",
                             "reduced": [], "why": "self-test"})
    for name, e2e in (("ingest-nan", "ingest_rows_per_s"),
                      ("boost-r25-nan", "boost_rounds_per_s")):
        bench["workloads"].append({"name": "bosch." + name,
                                   "config": "bosch", "traffic": name,
                                   "chips": 1, "why": "self-test"})
        for m in bench["end_to_end"]:
            if m["name"] == e2e:
                m["workloads"].append("bosch." + name)
    # the readers' entries: the boost cell's as shipped, the ingest
    # cell's as the PR that adds the cell will write them
    (split,) = [m for m in bench_json()["per_layer"]
                if m["name"] == "round.split_ms"]
    assert split["workloads"] == [BOOST]
    bench["per_layer"].append(dict(split, workloads=["bosch.boost-r25-nan"]))
    for reader in NEW_READERS[:2]:
        bench["per_layer"].append({
            "name": reader, "unit": "s/op", "better": "lower",
            "source": "device_trace", "layer": "ingest",
            "moves": "ingest_rows_per_s",
            "workloads": ["bosch.ingest-nan"]})
    json.dump(bench, open(f"{root}/BENCHMARK.json", "w"))
    return root, mixes


@pytest.mark.parametrize("mix, e2e", [("ingest-nan", "ingest_rows_per_s"),
                                      ("boost-r25-nan",
                                       "boost_rounds_per_s")])
def test_new_files_run_in_a_scratch_root(scratch, mix, e2e):
    root, mixes = scratch
    out, lines = run(root, "bosch." + mix)
    assert out["correct"] is True, lines
    assert out["metrics"][e2e]["value"] > 0
    assert set(out["metrics"]) == {e2e, "setup_s"}
    # the limits compared are the mix's, name for name
    assert set(out["compared"]) == set(mixes[mix]["limits"]) | {
        "window.compiles", "ops.failed"}
    assert out["compared"]["missing_bin_mismatches"]["value"] == 0


def test_a_traced_run_without_the_marks_leaves_the_new_metrics_out(
        scratch, monkeypatch):
    """The parent's program has no ``nan_scan`` span and no
    ``dmlc.cuts.finite`` scope: the readers return nothing, the line
    leaves the metrics out, nothing raises."""
    root, _ = scratch
    planes = {"/device:TPU:0": {xplane.OPS_LINE: [("fusion.2", 1.0, 1.5)],
                                xplane.MODULES_LINE: [("jit_a(1)", 1.0,
                                                       1.5)]},
              "/host:CPU": {"main": [("bench.window", 0.0, 2.0),
                                     ("bench.op", 0.5, 2.0)]}}
    monkeypatch.setattr(xplane, "load", lambda path: planes)
    monkeypatch.setattr(_spans, "load", lambda path: _spans.Marks(
        [], [[("", 1.0, 1.5)]]))
    for mix in ("ingest-nan", "boost-r25-nan"):
        out, lines = run(root, "bosch." + mix, trace=True)
        assert not set(NEW_READERS) & set(out["metrics"]), lines
        assert "compile.cache_misses" in out["metrics"]


# -- the readers ---------------------------------------------------------------------

read = test_spans.read


def test_split_reader_on_a_synthetic_round():
    ctx = test_spans.ctx_of(test_spans.DEVICE_OPS, [], [], ops=2, work=50.0)
    # two levels' splits of 0.5 s over 100 rounds
    assert read(ctx, "round.split_ms") == pytest.approx(1e3 * 1.0 / 100)
    none = test_spans.ctx_of([("", 1.0, 9.0), ("dmlc.round.L0.hist", 1.0,
                                               4.0)], [], [])
    assert read(none, "round.split_ms") is None
    # the leaf's and another layer's scopes are not a level's split
    other = test_spans.ctx_of([("dmlc.round.leaf", 1.0, 2.0),
                               ("dmlc.predict.split", 2.0, 3.0)], [], [])
    assert read(other, "round.split_ms") is None


def test_ingest_readers_on_a_synthetic_ingest():
    # two operations: the scan 1 s idle each; the finite summary 2 s of
    # device time each inside dmlc.cuts, whose own remainder is 0.25 s
    ops = [("dmlc.cuts.finite", 1.0, 3.0), ("dmlc.cuts", 3.0, 3.25),
           ("dmlc.bin", 3.25, 4.0),
           ("dmlc.cuts.finite", 6.0, 8.0), ("dmlc.cuts", 8.0, 8.25),
           ("dmlc.bin", 8.25, 9.0)]
    spans = [("dmlc.ingest", 0.0, 4.0, 1),
             ("dmlc.ingest.host_prep", 0.0, 1.0, 1),
             ("dmlc.ingest.host_prep.nan_scan", 0.0, 1.0, 1),
             ("dmlc.ingest", 5.0, 9.0, 2),
             ("dmlc.ingest.host_prep", 5.0, 6.0, 2),
             ("dmlc.ingest.host_prep.nan_scan", 5.0, 6.0, 2)]
    ctx = test_spans.ctx_of(ops, spans, [], ops=2)
    assert read(ctx, "ingest.idle_s.nan_scan") == pytest.approx(1.0)
    assert read(ctx, "ingest.cuts_finite_device_s") == pytest.approx(2.0)
    assert read(ctx, "ingest.cuts_device_s") == pytest.approx(0.25)
    # the dense program: neither the span nor the scope
    dense = test_spans.ctx_of(
        [("dmlc.cuts", 1.0, 3.0)],
        [("dmlc.ingest", 0.0, 4.0, 1),
         ("dmlc.ingest.host_prep", 0.0, 1.0, 1)], [], ops=1)
    assert read(dense, "ingest.idle_s.nan_scan") is None
    assert read(dense, "ingest.cuts_finite_device_s") is None


# -- the data rule ---------------------------------------------------------------------

def test_the_table_is_the_seeds_on_any_number_of_threads(monkeypatch):
    a = datagen_missing.bosch_like(70000, 64, SEED)
    monkeypatch.setattr(datagen_missing, "_THREADS", 1)
    b = datagen_missing.bosch_like(70000, 64, SEED)
    assert np.array_equal(a[0], b[0], equal_nan=True)
    assert np.array_equal(a[1], b[1])
    X, y = a
    assert abs(np.isnan(X).mean() - 0.81) < 0.01
    assert 0.004 < y.mean() < 0.008
    assert np.isfinite(X).any(axis=0).all()
    line = datagen_missing.line_of(64, SEED)
    skipped = np.isnan(X[:, line.a])
    # the tenth of the parts that skipped station A holds most failures
    assert 0.07 < skipped.mean() < 0.13
    assert y[skipped].sum() > 0.7 * y.sum()
    # a large seed, and another stream of the same line
    Xh, _ = datagen_missing.bosch_like(5000, 64, 2**31 + 5, stream=1)
    X0, _ = datagen_missing.bosch_like(5000, 64, 2**31 + 5, stream=0)
    assert not np.array_equal(Xh, X0, equal_nan=True)


# -- the program and the controls ----------------------------------------------------

CFG = dict(util.TINY_CONFIG, rows=60000, features=64, n_bins=64,
           n_summary=8 * 63, max_depth=6, learning_rate=0.1)


@pytest.fixture(scope="module")
def fitted():
    from dmlc_core_tpu.models import HistGBT

    X, y = datagen_missing.bosch_like(CFG["rows"], CFG["features"], SEED)
    model = HistGBT(n_trees=4, max_depth=CFG["max_depth"],
                    n_bins=CFG["n_bins"],
                    learning_rate=CFG["learning_rate"],
                    objective="binary:logistic")
    handle = model.make_device_data(X, y)
    model.fit_device(handle)
    trees = [{k: np.asarray(v) for k, v in t.items()} for t in model.trees]
    bins_t = np.asarray(handle["bins_t"])[:, :len(y)]
    return X, y, trees, bins_t, np.asarray(model.cuts), model


def test_the_reference_routes_as_the_program_predicts(fitted):
    X, y, trees, bins_t, cuts, model = fitted
    assert model.round_plan["missing"] is True and "dir" in trees[0]
    margin = ref.ensemble_margin(X[:4096], cuts, trees, CFG["base_score"])
    assert np.abs(ref.sigmoid(margin) - model.predict(X[:4096])).max() < 1e-5
    # binned and raw descent agree row for row, NaN by the direction
    node_b = ref.descend_binned(bins_t[:, :4096], trees[0],
                                CFG["n_bins"] - 1)
    node_r = ref.descend_raw(X[:4096].astype(np.float64),
                             cuts.astype(np.float64), trees[0])
    assert np.array_equal(node_b, node_r)
    # the root: station A's column, the rows without it to the right
    line = datagen_missing.line_of(CFG["features"], SEED)
    assert trees[0]["feat"][0, 0] == line.a and trees[0]["dir"][0, 0] == 0


def test_the_program_keeps_the_limits_that_no_size_moves(fitted):
    X, y, trees, bins_t, cuts, _model = fitted
    got = checks_missing.boost_tree_numbers(bins_t, y, trees, CFG)
    assert set(got) | {"rounds_share", "bins_mismatches",
                       "missing_bin_mismatches", "ops_trees_differ",
                       "train_logloss", "heldout_auc"} == \
        set(shipped("traffic", "boost-r25-nan")["limits"])
    for name in ("tree0.root_gain_gap", "tree0.root_dir_differs",
                 "tree0.leaf_gap"):
        assert got[name] <= limit("boost-r25-nan", name), (name, got)
    nums = checks_missing.bin_numbers(X[:4096], bins_t[:, :4096], cuts, CFG)
    assert nums == {"bins_mismatches": 0, "missing_bin_mismatches": 0}
    assert checks_missing.cuts_gap(X, cuts, [0, 9, 33, 63], CFG) <= \
        limit("ingest-nan", "cuts_gap")
    assert set(nums) | {"rows_share", "cuts_gap"} == \
        set(shipped("traffic", "ingest-nan")["limits"])


@pytest.mark.parametrize("control, fails", [
    ("force_left", "tree0.root_gain_gap"), ("bfloat16", "tree0.leaf_gap"),
    ("float8", "tree1.leaf_gap_by_rows")])
def test_each_tree_control_leaves_a_limit(fitted, control, fails):
    X, y, trees, bins_t, cuts, _model = fitted
    got = checks_missing.boost_tree_numbers(
        bins_t, y, checks_missing.control_trees(bins_t, y, trees, CFG,
                                                control), CFG)
    assert got[fails] > limit("boost-r25-nan", fails), got


def test_each_ingest_control_leaves_a_limit(fitted):
    X, y, trees, bins_t, cuts, _model = fitted
    k = 4096
    aliased = ref.bin_rows(X[:k], cuts, alias_missing=True).T
    nums = checks_missing.bin_numbers(X[:k], aliased, cuts, CFG)
    assert nums["missing_bin_mismatches"] == np.isnan(X[:k]).sum() > \
        limit("ingest-nan", "missing_bin_mismatches")
    assert nums["bins_mismatches"] == nums["missing_bin_mismatches"]
    rows16 = ref.bin_rows(X[:k], cuts, precision="bfloat16").T
    nums = checks_missing.bin_numbers(X[:k], rows16, cuts, CFG)
    assert nums["bins_mismatches"] > limit("ingest-nan", "bins_mismatches")
    assert nums["missing_bin_mismatches"] == 0
    cuts16 = np.stack([ref.quantile_cuts(X[:, f], CFG["n_bins"],
                                         CFG["n_summary"], "bfloat16")
                       for f in (0, 9)])
    assert checks_missing.cuts_gap(X[:, [0, 9]], cuts16, [0, 1], CFG) > \
        limit("ingest-nan", "cuts_gap")


def test_forced_left_directions_show_in_what_the_ensemble_learns(fitted):
    X, y, trees, bins_t, cuts, _model = fitted
    Xh, yh = datagen_missing.bosch_like(20000, CFG["features"], SEED,
                                        stream=1)
    sound = checks_missing.learning_numbers(X[:8192], y[:8192], Xh, yh, cuts,
                                            trees, CFG)
    forced = checks_missing.learning_numbers(X[:8192], y[:8192], Xh, yh,
                                             cuts, trees, CFG,
                                             force_left=True)
    assert sound["heldout_auc"] > 0.8 > forced["heldout_auc"]
    assert checks.trees_differ(trees, trees) == 0
