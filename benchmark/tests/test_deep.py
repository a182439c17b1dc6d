"""What ISSUE 37 adds to the benchmark is data and one reader: the deep
configuration (HIGGS at gbm-bench's depth 8 and eta 0.1), a traffic mix
whose limits are the cell's own, and ``round.nblock_ms``.  Here, on the
CPU at toy size: they load and run in a scratch root as files only (the
shipped files, their sizes cut); the mix's limits name every number the
``boost`` op's check produces; a small depth-8 fit keeps every limit of
the mix against ``reference.py`` and each control leaves one; the reader
reads a synthetic trace and is silent on a program without the scope."""

import json
import os
import shutil

import numpy as np
import pytest

import test_spans
import util
from benchmark import checks, datagen, harness, reference as ref, xplane
from benchmark.metrics import _spans
from test_wide import BENCH, ROOT, limit, shipped

SEED = 2**31 + 37
CELL, CONFIG, MIX = ("higgs-24m-d8.boost-r25-eta01", "higgs-24m-d8",
                     "boost-r25-eta01")


# -- the shipped files -------------------------------------------------------------

def test_the_deep_configuration_is_the_flagships_but_for_depth_and_rate():
    deep, flag = shipped("configs", CONFIG), shipped("configs",
                                                     "higgs-24m-d6")
    assert set(deep) == set(flag)
    differ = {k for k in flag if deep[k] != flag[k]}
    assert differ == {"source", "deployment", "max_depth", "learning_rate",
                      "assumed"}
    assert (deep["max_depth"], deep["learning_rate"], deep["reg_lambda"],
            deep["n_bins"]) == (8, 0.1, 1.0, 256)
    assert (deep["rows"], deep["features"]) == (24_000_000, 28)
    assert deep["reduced"] == [] and deep["chips"] == 1
    assert len(deep["source"]) <= 200 and "gbm-bench" in deep["source"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry["source"] == deep["source"] and entry["reduced"] == []
    assert entry["file"] == "benchmark/configs/higgs-24m-d8.json"
    # two deployments from one data set need sources that differ
    assert len({c["source"] for c in bench["configs"]}) == \
        len(bench["configs"])


def test_the_new_cell_is_an_entry_and_a_name_in_lists():
    # not on two lists that ISSUE 37 names, because the accepted readers
    # find nothing honest to read there: `dispatch.gap_ms.boost` is the
    # gap between two round programs, and the traced window (10 s; an op
    # is 16.6 s, the parent's 58) holds one; `hist.mxu_share` divides ALL
    # levels' flops by the Mosaic calls' time, and the parent builds this
    # cell's last level outside Mosaic: it would read 132% there
    metrics = {"boost_rounds_per_s", "hist.time_share", "round.hist_ms",
               "round.hist_ms.deepest", "round.nonhist_ms",
               "round.nblock_ms"}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        (CONFIG, MIX, 1)
    assert len(entry["why"]) <= 200
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == metrics
    # the mix is the accepted operation's with boost-r25's parameters
    # exactly; its limits name what the op's check produces, each with
    # the readings it was set from
    new, r25 = shipped("traffic", MIX), shipped("traffic", "boost-r25")
    assert new["op"] == "boost" and new["params"] == r25["params"]
    assert new["end_to_end"] == r25["end_to_end"]
    assert new["trace_seconds"] == r25["trace_seconds"]
    assert set(new["limits"]) == set(r25["limits"])
    assert set(new["limits"]) - {"rounds_share"} <= set(new["limits_from"])
    assert harness.metrics_of(bench, "end_to_end", CELL)[-1]["name"] == \
        "setup_s"


# -- in a scratch root, as files only ------------------------------------------------

def run(root, cell, trace=False):
    lines = []
    out = harness.run_cell(root, cell, SEED, 0.3, trace, require_chip=False,
                           say=lines.append)
    return out, lines


def test_new_files_run_in_a_scratch_root(tmp_path, monkeypatch):
    """The shipped configuration, mix and reader, copied beside the
    scratch root's own files with their SIZES cut to a test's, and
    entries in its BENCHMARK.json: the cell runs at depth 8 and is
    correct, and the limits compared are the mix's, name for name."""
    root = util.make_root(tmp_path)
    base = os.path.join(root, "bench_data")
    deep = dict(shipped("configs", CONFIG), rows=20000, features=8,
                heldout_rows=2048, n_bins=32, n_summary=256)
    assert deep["max_depth"] == 8
    json.dump(deep, open(f"{base}/configs/deep.json", "w"))
    mix = shipped("traffic", MIX)
    mix["params"] = dict(mix["params"], n_trees=3, warm_trees=3,
                         check_bin_rows=256, check_heldout_rows=512,
                         check_train_rows=512)
    # three rounds at eta 0.1 and toy size learn little: the two learning
    # limits are the toy's, every other limit the shipped file's
    mix["limits"] = dict(mix["limits"], train_logloss=0.69,
                         heldout_auc={"limit": 0.55, "passes": "at_least"})
    json.dump(mix, open(f"{base}/traffic/{MIX}.json", "w"))
    shutil.copy(os.path.join(BENCH, "metrics", "round.nblock_ms.py"),
                f"{base}/metrics/round.nblock_ms.py")
    bench = json.load(open(f"{root}/BENCHMARK.json"))
    bench["configs"].append({"name": "deep", "source": deep["source"],
                             "file": "bench_data/configs/deep.json",
                             "reduced": [], "why": "self-test"})
    bench["workloads"].append({"name": "deep." + MIX, "config": "deep",
                               "traffic": MIX, "chips": 1,
                               "why": "self-test"})
    for m in bench["end_to_end"]:
        if m["name"] == "boost_rounds_per_s":
            m["workloads"].append("deep." + MIX)
    bench["per_layer"].append(
        {"name": "round.nblock_ms", "unit": "ms/round", "better": "lower",
         "source": "device_trace", "layer": "kernels",
         "moves": "boost_rounds_per_s", "workloads": ["deep." + MIX]})
    json.dump(bench, open(f"{root}/BENCHMARK.json", "w"))

    out, lines = run(root, "deep." + MIX)
    assert out["correct"] is True, lines
    assert out["metrics"]["boost_rounds_per_s"]["value"] > 0
    assert set(out["compared"]) == set(mix["limits"]) | {"window.compiles",
                                                         "ops.failed"}

    # traced, on a program whose trace has no dmlc.hist.nblock scope (the
    # parent's, and the CPU's segment engine): the metric is left out
    planes = {"/device:TPU:0": {xplane.OPS_LINE: [("fusion.2", 1.0, 1.5)],
                                xplane.MODULES_LINE: [("jit_a(1)", 1.0,
                                                       1.5)]},
              "/host:CPU": {"main": [("bench.window", 0.0, 2.0),
                                     ("bench.op", 0.5, 2.0)]}}
    monkeypatch.setattr(xplane, "load", lambda path: planes)
    monkeypatch.setattr(_spans, "load", lambda path: _spans.Marks(
        [], [[("", 1.0, 1.5)]]))
    out, lines = run(root, "deep." + MIX, trace=True)
    assert "round.nblock_ms" not in out["metrics"]
    assert "compile.cache_misses" in out["metrics"]


# -- the reader --------------------------------------------------------------------

def ctx_of(device_ops, ops=2, work=25.0):
    return test_spans.ctx_of(device_ops, [], [], ops=ops, work=work)


read = test_spans.read


def test_nblock_reader_on_a_synthetic_node_blocked_round():
    # a scan 1..9; level 7's two kernels, each block's node map before
    # it and the join after them under dmlc.hist.nblock
    ops = [("", 1.0, 9.0),
           ("dmlc.round.L6.hist", 1.0, 3.0),
           ("dmlc.hist.nblock", 3.0, 3.25),
           ("dmlc.round.L7.hist", 3.25, 5.25),
           ("dmlc.hist.nblock", 5.25, 5.5),
           ("dmlc.round.L7.hist", 5.5, 7.5),
           ("dmlc.hist.nblock", 7.5, 8.0)]
    ctx = ctx_of(ops)                                  # 50 rounds
    assert read(ctx, "round.nblock_ms") == pytest.approx(1e3 * 1.0 / 50)
    # the scope is its own: the level's kernels do not count it twice
    assert read(ctx, "round.hist_ms") == pytest.approx(1e3 * 6.0 / 50)
    assert read(ctx, "round.hist_ms.deepest") == pytest.approx(1e3 * 4.0 / 50)
    assert read(ctx, "round.nonhist_ms") == pytest.approx(1e3 * 2.0 / 50)


def test_nblock_reader_is_silent_without_the_scope():
    ctx = ctx_of([("", 1.0, 9.0), ("dmlc.round.L0.hist", 1.0, 4.0)])
    assert read(ctx, "round.nblock_ms") is None
    assert read(ctx_of([("", 1.0, 5.0)]), "round.nblock_ms") is None
    assert read(ctx_of([("dmlc.hist.nblock", 1.0, 2.0)], ops=0),
                "round.nblock_ms") is None
    # ... and on a program that blocks on features alone
    assert read(ctx_of([("dmlc.hist.fblock", 1.0, 2.0)]),
                "round.nblock_ms") is None


# -- the program and the controls, at depth 8 ----------------------------------------

CFG = dict(util.TINY_CONFIG, rows=60000, features=28, n_bins=64,
           n_summary=512, max_depth=8, learning_rate=0.1)


@pytest.fixture(scope="module")
def deep():
    """The program fitted on the CPU at 60,000 x 28, depth 8, eta 0.1:
    256 leaves of ~230 rows."""
    from dmlc_core_tpu.models import HistGBT

    X, y = datagen.higgs_like(CFG["rows"], CFG["features"], 37)
    model = HistGBT(n_trees=4, max_depth=CFG["max_depth"],
                    n_bins=CFG["n_bins"],
                    learning_rate=CFG["learning_rate"])
    handle = model.make_device_data(X, y)
    model.fit_device(handle)
    trees = [{k: np.asarray(v) for k, v in t.items()} for t in model.trees]
    bins_t = np.asarray(handle["bins_t"])[:, :len(y)]
    return X, y, trees, bins_t, np.asarray(model.cuts), model


BOOST_NUMBERS = ["tree0.root_gain_gap", "tree0.reported_gain_gap",
                 "tree0.leaf_gap", "tree1.leaf_gap"]


def test_the_reference_serves_depth_8_as_it_is(deep):
    X, y, trees, bins_t, cuts, model = deep
    assert trees[0]["feat"].shape == (8, 128) and len(trees[0]["leaf"]) == 256
    node = ref.descend_binned(bins_t, trees[0]["feat"], trees[0]["thr"])
    assert node.max() < 256 and len(np.unique(node)) > 128
    # the reference's own descent on raw values is the program's predict
    margin = ref.ensemble_margin(X[:2048], cuts, trees, CFG["base_score"])
    assert np.abs(ref.sigmoid(margin) - model.predict(X[:2048])).max() < 1e-5


def test_the_program_keeps_every_limit_of_the_deep_mix(deep):
    X, y, trees, bins_t, cuts, _model = deep
    got = checks.boost_tree_numbers(bins_t, y, trees, CFG)
    for name in BOOST_NUMBERS:
        assert got[name] <= limit(MIX, name), (name, got)
    assert checks.bins_mismatches(X[:4096], bins_t[:, :4096], cuts) == \
        limit(MIX, "bins_mismatches")


@pytest.mark.parametrize("control, fails", [
    ("bfloat16", {"tree0.leaf_gap"}), ("float8", {"tree1.leaf_gap"})])
def test_lower_precision_sums_and_gradients_leave_a_limit(deep, control,
                                                          fails):
    X, y, trees, bins_t, cuts, _model = deep
    got = checks.boost_tree_numbers(
        bins_t, y, checks.control_trees(bins_t, y, trees, CFG, control), CFG)
    failed = {n for n in BOOST_NUMBERS if got[n] > limit(MIX, n)}
    assert fails <= failed, got


def test_bfloat16_rows_leave_a_limit(deep):
    X, y, trees, bins_t, cuts, _model = deep
    k = shipped("traffic", MIX)["params"]["check_bin_rows"]
    rows16 = ref.bin_rows(X[:k], cuts, precision="bfloat16").T
    assert checks.bins_mismatches(X[:k], rows16, cuts) > \
        limit(MIX, "bins_mismatches")
