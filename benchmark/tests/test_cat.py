"""What ISSUE 58 adds to the benchmark, on the CPU at toy size: the
native-categorical configuration (LightGBM's Expo table fed as its 8 raw
columns), its rows, its plain reference and check, the operation and the
seam that carry the three hyperparameters, the mix, three readers.  The
shipped files load and run in a scratch root as files only (their sizes
cut); the mix's limits name every number the check produces; the program
keeps every limit that does not depend on the size and each control
leaves the limit named for it; the readers read a synthetic trace and are
silent on a program without their scopes.
"""

import json
import os
import shutil

import numpy as np
import pytest

import util
from benchmark import (checks_cat as cc, datagen_cat, harness, peaks,
                       reference as ref, reference_cat as rc, xplane)
from benchmark.metrics import _spans

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
SEED = 2**31 + 58
CONFIG = "expo-115m-cat-d8"
MIX = "boost-r10-cat"
CELL = f"{CONFIG}.{MIX}"
NEW_READERS = ["round.cat_split_ms", "round.cat_route_ms",
               "setup.ingest_cats_s"]


def shipped(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def bench_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# -- the shipped files -------------------------------------------------------------

def test_the_configuration_is_the_sources_uncut():
    cfg = shipped("configs", CONFIG)
    assert (cfg["rows"], cfg["features"], cfg["max_depth"],
            cfg["learning_rate"], cfg["min_child_weight"], cfg["n_bins"],
            cfg["max_cat_to_onehot"], cfg["max_cat_threshold"],
            cfg["objective"], cfg["base_score"], cfg["reg_lambda"]) == (
        115000000, 8, 8, 0.1, 100.0, 256, 4, 64, "binary:logistic", 0.0, 1.0)
    assert cfg["feature_types"] == list(datagen_cat.FEATURE_TYPES)
    assert cfg["cardinalities"] == list(datagen_cat.CARDINALITIES)
    assert sum(cfg["cardinalities"]) + 2 == 700          # the one-hot width
    assert cfg["reduced"] == [] and cfg["chips"] == 1
    assert "byte-identical" in cfg["guarantees"]
    assert "falling count" in cfg["guarantees"]
    assert len(cfg["source"]) <= 200 and "Experiments.rst" in cfg["source"]
    assert any("from memory" in a for a in cfg["assumed"])
    assert any("x10.5" in a for a in cfg["assumed"])
    bench = bench_json()
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry["source"] == cfg["source"] and entry["reduced"] == []
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert len({c["source"] for c in bench["configs"]}) == \
        len(bench["configs"])


def test_the_cell_is_an_entry_and_a_name_in_lists():
    bench = bench_json()
    (entry,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, MIX, 1)
    assert len(entry["why"]) <= 200
    assert [w for w in bench["workloads"] if w["config"] == CONFIG] == [entry]
    assert [m["name"] for m in harness.metrics_of(
        bench, "end_to_end", CELL)] == ["boost_rounds_per_s", "setup_s"]
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert set(NEW_READERS) | {
        "hist.time_share", "hist.mxu_share", "round.hist_ms",
        "round.hist_ms.deepest", "round.nonhist_ms", "round.nblock_ms",
        "round.split_ms", "setup.ingest_s", "setup.fit_s"} <= listed
    for m in harness.metrics_of(bench, "per_layer", CELL):
        harness.find_file(ROOT, bench["paths"], "metrics", m["name"] + ".py")
        assert m["moves"] in ("boost_rounds_per_s", "setup_s"), m
    for m in bench["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL]
    cells = bench["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= len(cells) // 4
    assert bench["run_seconds"] == 20


def test_the_mix_says_where_each_limit_comes_from():
    mix = shipped("traffic", MIX)
    assert mix["op"] == "boost_cat"
    assert mix["end_to_end"] == {"boost_rounds_per_s": {"kind": "rate"}}
    assert mix["params"]["n_trees"] == mix["params"]["warm_trees"] == 10
    assert (mix["params"]["check_bin_rows"],
            mix["params"]["check_heldout_rows"],
            mix["params"]["check_train_rows"]) == (4096, 65536, 65536)
    assert mix["trace_seconds"] == 10
    assert set(mix["limits"]) - {"rounds_share"} <= set(mix["limits_from"])
    assert "PR 58" in mix["limits_from"]["readings"]
    assert mix["limits"]["tree0.set_over"] == 0
    assert mix["limits"]["tree0.min_child_hessian"]["limit"] == 100.0


def test_the_window_is_the_accepted_operation():
    """``ops/boost_cat.py``'s ``op`` (the timed part) is ``boost.py``'s,
    line for line; the reference and the check import nothing of the
    program, and the seam makes the model before a row is drawn."""
    import inspect

    def mod(name):
        return harness.load_module(os.path.join(BENCH, "ops", name + ".py"))

    assert (inspect.getsource(mod("boost_cat").op)
            == inspect.getsource(mod("boost").op))
    for name in ("reference_cat.py", "checks_cat.py", "datagen_cat.py"):
        assert "dmlc_core_tpu" not in open(os.path.join(BENCH, name)).read()
    setup = inspect.getsource(mod("boost_cat").setup)
    assert setup.index("new_model") < setup.index("training_rows")


# -- in a scratch root, as files only ------------------------------------------------

TOY = dict(rows=60000, heldout_rows=4096, max_depth=4, min_child_weight=20.0)


def run(root, cell, trace=False):
    lines = []
    out = harness.run_cell(root, cell, SEED, 0.3, trace, require_chip=False,
                           say=lines.append)
    return out, lines


@pytest.fixture(scope="module")
def scratch(tmp_path_factory):
    """The shipped configuration and mix in a scratch root with their
    SIZES cut to a test's, the shipped readers, and entries in its
    BENCHMARK.json.  The operation is found beside the harness."""
    root = util.make_root(tmp_path_factory.mktemp("cat"))
    base = os.path.join(root, "bench_data")
    cfg = dict(shipped("configs", CONFIG), **TOY)
    json.dump(cfg, open(f"{base}/configs/toy.json", "w"))
    mix = shipped("traffic", MIX)
    mix["params"] = dict(mix["params"], n_trees=3, warm_trees=3,
                         check_bin_rows=512, check_heldout_rows=4096,
                         check_train_rows=4096)
    # three rounds on 60,000 rows learn little: the toy's own limits for
    # what depends on the size, the shipped file's for the rest
    mix["limits"] = dict(
        mix["limits"], train_logloss=0.69,
        heldout_auc={"limit": 0.55, "passes": "at_least"},
        **{"tree0.min_child_hessian": {"limit": 20.0,
                                       "passes": "at_least"}})
    json.dump(mix, open(f"{base}/traffic/{MIX}.json", "w"))
    for reader in NEW_READERS:
        shutil.copy(os.path.join(BENCH, "metrics", reader + ".py"),
                    f"{base}/metrics/{reader}.py")
    bench = json.load(open(f"{root}/BENCHMARK.json"))
    bench["configs"].append({"name": "toy", "source": cfg["source"],
                             "file": "bench_data/configs/toy.json",
                             "reduced": [], "why": "self-test"})
    cell = f"toy.{MIX}"
    bench["workloads"].append({"name": cell, "config": "toy", "traffic": MIX,
                               "chips": 1, "why": "self-test"})
    for m in bench["end_to_end"]:
        if m["name"] == "boost_rounds_per_s":
            m["workloads"].append(cell)
    for m in bench_json()["per_layer"]:
        if m["name"] in NEW_READERS:
            bench["per_layer"].append(dict(m, workloads=[cell]))
    json.dump(bench, open(f"{root}/BENCHMARK.json", "w"))
    return root, mix, cell


def test_new_files_run_in_a_scratch_root(scratch):
    root, mix, cell = scratch
    out, lines = run(root, cell)
    assert out["correct"] is True, lines
    assert out["metrics"]["boost_rounds_per_s"]["value"] > 0
    assert set(out["metrics"]) == {"boost_rounds_per_s", "setup_s"}
    # the limits compared are the mix's, name for name
    assert set(out["compared"]) == set(mix["limits"]) | {
        "window.compiles", "ops.failed"}
    assert out["compared"]["bins_mismatches"]["value"] == 0
    assert out["compared"]["tree0.set_over"]["value"] == 0
    assert out["compared"]["ops_trees_differ"]["value"] == 0
    assert any("on categorical columns" in ln for ln in lines), lines


def test_a_traced_run_leaves_out_what_has_no_scope(scratch, monkeypatch):
    """A trace without the program's scopes (the parent's, or here the
    CPU's): the three readers return nothing, the line leaves them out,
    nothing raises."""
    root, _, cell = scratch
    planes = {"/device:TPU:0": {
        xplane.OPS_LINE: [("fusion.2", 1.0, 1.5),
                          ("x custom-call/tpu_custom_call f32[8]", 1.5,
                           2.0)],
        xplane.MODULES_LINE: [("jit_a(1)", 1.0, 2.0)]},
        "/host:CPU": {"main": [("bench.window", 0.0, 2.0),
                               ("bench.op", 0.5, 2.0)]}}
    monkeypatch.setattr(xplane, "load", lambda path: planes)
    monkeypatch.setattr(_spans, "load", lambda path: _spans.Marks(
        [], [[("", 1.0, 2.0)]]))
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    out, lines = run(root, cell, trace=True)
    assert out["correct"] is True, lines
    assert not set(NEW_READERS) & set(out["metrics"]), lines
    assert "compile.cache_misses" in out["metrics"]


def test_readers_on_a_synthetic_round():
    """The two span readers sum the self time under their scopes, the
    leaf tail's lookup included, over the rounds; scopes that are not
    theirs count for nothing."""
    scoped = [("dmlc.round.L3.split.cat", 0.0, 0.010),
              ("dmlc.round.L3.split", 0.010, 0.030),
              ("dmlc.round.L4.route.cat", 0.030, 0.090),
              ("dmlc.round.leaf.route.cat", 0.090, 0.150),
              ("dmlc.round.L4.route", 0.150, 0.190)]
    ctx = harness.Ctx(root=ROOT, workload="w", config={}, mix={}, seed=0,
                      chips=1)
    ctx.op_work = [10.0]
    ctx.state["_spans.by_scope"] = None

    def read(name, monkey):
        mod = harness.load_module(os.path.join(BENCH, "metrics",
                                               name + ".py"))
        return mod.read(ctx)

    by = {}
    for s, a, b in scoped:
        by[s] = by.get(s, 0.0) + (b - a)
    import unittest.mock as mock
    with mock.patch.object(_spans, "by_scope", lambda c: by):
        assert read("round.cat_split_ms", None) == pytest.approx(1.0)
        assert read("round.cat_route_ms", None) == pytest.approx(12.0)
    with mock.patch.object(_spans, "by_scope",
                           lambda c: {"dmlc.round.L1.split": 1.0}):
        assert read("round.cat_split_ms", None) is None
        assert read("round.cat_route_ms", None) is None


# -- the check and its controls ------------------------------------------------------

@pytest.fixture(scope="module")
def fitted():
    """A toy fit through the seam, and what the check reads of it."""
    from benchmark import system, system_cat

    cfg = dict(shipped("configs", CONFIG), **TOY)
    ctx = harness.Ctx(root=ROOT, workload="w", config=cfg, mix={},
                      seed=SEED, chips=1)
    model = system_cat.new_model(ctx, 3)
    assert list(model.param.feature_types) == cfg["feature_types"]
    X, y = system_cat.training_rows(ctx)
    handle = system.ingest(model, X, y)
    model.fit_device(handle)
    bins_t = np.asarray(handle["bins_t"])[:, :len(y)]
    return (cfg, X, bins_t, y, system.host_trees(model.trees),
            np.asarray(model.cuts), ctx)


def _limits():
    limits = shipped("traffic", MIX)["limits"]
    return {k: (v if isinstance(v, dict) else {"limit": v})
            for k, v in limits.items()}


def _broken(numbers, **own):
    out = []
    for name, value in numbers.items():
        lim = dict(_limits()[name])
        lim["limit"] = own.get(name, lim["limit"])
        ok = (value >= lim["limit"] if lim.get("passes") == "at_least"
              else value <= lim["limit"])
        if not ok:
            out.append(name)
    return sorted(out)


TOY_MCW = {"tree0.min_child_hessian": 20.0}


def test_the_program_keeps_every_limit(fitted):
    cfg, X, bins_t, y, trees, cuts, _ = fitted
    numbers, facts = cc.tree_numbers(bins_t, y, trees, cuts, cfg)
    assert _broken(numbers, **TOY_MCW) == [], numbers
    assert facts["cat_split_share"] > 0.5 and facts["largest_set"] <= 64
    assert cc.tables_mismatches(
        {f: X[:, f] for f, t in enumerate(cfg["feature_types"])
         if t == "c"}, cuts, 256) == 0
    assert cc.bins_mismatches(X[:512], bins_t[:, :512], cuts,
                              cfg["feature_types"]) == 0
    # grown under 20, held to the configuration's 100
    assert _broken(numbers) in ([], ["tree0.min_child_hessian"])


def test_codes_read_as_an_order_leave_the_best_gain(fitted):
    """The same rows fitted with every column numeric: at some node the
    rule finds a partition no threshold on the codes reaches."""
    from benchmark import system, system_cat

    cfg, X, bins_t, y, _, cuts, ctx = fitted
    model = system_cat.new_model(ctx, 3, feature_types=[])
    handle = system.ingest(model, X, y)
    model.fit_device(handle)
    bins_q = np.asarray(handle["bins_t"])[:, :len(y)]
    trees = [cc.as_sets(t, 256) for t in system.host_trees(model.trees)]
    used = rc.used_bins(cuts, cfg["feature_types"])
    numbers, _ = cc.tree_numbers(
        bins_q, y, trees, np.asarray(model.cuts),
        dict(cfg, feature_types=["q"] * 8), rule=(bins_t, used))
    assert "tree0.best_gain_gap" in _broken(numbers, **TOY_MCW), numbers
    assert numbers["tree0.best_gain_gap"] > 1e-3
    assert numbers["tree0.leaf_gap"] < 1e-5      # its leaves are sound


def test_a_set_shifted_by_one_bin_leaves_the_leaves(fitted):
    cfg, X, bins_t, y, trees, cuts, _ = fitted
    shifted = [cc.shifted_sets(trees[0], cuts, cfg)] + trees[1:]
    numbers, _ = cc.tree_numbers(bins_t, y, shifted, cuts, cfg)
    assert "tree0.leaf_gap" in _broken(numbers, **TOY_MCW), numbers


@pytest.mark.parametrize("precision, leaves", [
    ("bfloat16", {"tree0.reported_gain_gap"}),
    ("float8", {"tree1.leaf_gap_by_rows"})])
def test_a_lower_precision_leaves_a_limit(fitted, precision, leaves):
    cfg, X, bins_t, y, trees, cuts, _ = fitted
    control = cc.control_trees(bins_t, y, trees, cuts, cfg, precision)
    numbers, _ = cc.tree_numbers(bins_t, y, control + trees[2:], cuts, cfg)
    assert leaves <= set(_broken(numbers, **TOY_MCW)), numbers


def test_a_set_over_the_limit_is_counted(fitted):
    cfg, X, bins_t, y, trees, cuts, _ = fitted
    numbers, _ = cc.tree_numbers(bins_t, y, trees, cuts,
                                 dict(cfg, max_cat_threshold=4))
    assert numbers["tree0.set_over"] > 0


def test_learning_numbers_go_through_predict(fitted):
    from benchmark import system_cat

    cfg, X, bins_t, y, trees, cuts, ctx = fitted
    Xh, yh = system_cat.heldout_rows(ctx, 4096)
    model = system_cat.new_model(ctx, 3)
    model.cuts, model.trees = cuts, trees
    got = cc.learning_numbers(X[:4096], y[:4096], yh, model.predict(Xh),
                              cuts, trees, cfg)
    assert got["train_logloss"] < 0.69 and got["heldout_auc"] > 0.55
    want = ref.auc(rc.ensemble_margin(Xh, cuts, cfg["feature_types"], trees,
                                      0.0, 256), yh)
    assert got["heldout_auc"] == pytest.approx(want, abs=1e-9)
