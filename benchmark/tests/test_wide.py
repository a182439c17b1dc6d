"""What ISSUE 35 adds to the benchmark is data and one reader: the wide
configuration, a traffic mix, ``round.fblock_ms`` (the queued whole-table
scoring cell was left out: its check does not fit a one-chip machine's
host memory, PERF.md section 7).  Here, on the CPU
at toy size: they load and run in a scratch root as files only (the
shipped files, their sizes cut); every control leaves a limit at a wide
shape; the reader reads a synthetic trace and is silent on a program
without the scope; ``wide_on_chip.py`` runs end to end."""

import json
import os
import shutil

import numpy as np
import pytest

import test_spans
import util
from benchmark import checks, datagen, harness, reference as ref, xplane
from benchmark.metrics import _spans

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
SEED = 2**31 + 35


def shipped(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def limit(mix, name):
    lim = shipped("traffic", mix)["limits"][name]
    return lim["limit"] if isinstance(lim, dict) else lim


# -- the shipped files -------------------------------------------------------------

def test_the_wide_configuration_is_the_flagships_but_for_its_shape():
    wide, flag = shipped("configs", "epsilon-400k-d6"), \
        shipped("configs", "higgs-24m-d6")
    assert set(wide) == set(flag)
    differ = {k for k in flag if wide[k] != flag[k]}
    assert differ == {"source", "deployment", "rows", "features",
                      "heldout_rows", "assumed"}
    assert (wide["rows"], wide["features"], wide["heldout_rows"]) == \
        (400_000, 2000, 100_000)
    assert wide["reduced"] == [] and wide["chips"] == 1
    assert len(wide["source"]) <= 200
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [c for c in bench["configs"] if c["name"] == "epsilon-400k-d6"]
    assert entry["source"] == wide["source"] and entry["reduced"] == []
    assert entry["file"] == "benchmark/configs/epsilon-400k-d6.json"


def test_the_new_cell_is_an_entry_and_a_name_in_lists():
    cell, config, mix, op = ("epsilon-400k-d6.boost-r25", "epsilon-400k-d6",
                             "boost-r25", "boost")
    metrics = {"boost_rounds_per_s", "hist.time_share", "hist.mxu_share",
               "round.hist_ms", "round.hist_ms.deepest", "round.nonhist_ms",
               "round.fblock_ms", "dispatch.gap_ms.boost"}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [w for w in bench["workloads"] if w["name"] == cell]
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        (config, mix, 1)
    assert len(entry["why"]) <= 200
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if cell in m.get("workloads", ())}
    assert listed == metrics
    # the mix is the accepted operation's, parameters alone, and its
    # end-to-end arithmetic and limits name what the old mix's do
    new, old = shipped("traffic", mix), shipped("traffic", op)
    assert new["op"] == op and set(new["params"]) == set(old["params"])
    assert new["end_to_end"] == old["end_to_end"]
    assert set(new["limits"]) == set(old["limits"])
    assert harness.metrics_of(bench, "end_to_end", cell)[-1]["name"] == \
        "setup_s"


# -- in a scratch root, as files only ------------------------------------------------

def run(root, cell, trace=False):
    lines = []
    out = harness.run_cell(root, cell, SEED, 0.3, trace, require_chip=False,
                           say=lines.append)
    return out, lines


def test_new_files_run_in_a_scratch_root(tmp_path, monkeypatch):
    """The shipped configuration, mix and reader, copied beside the
    scratch root's own files with their SIZES cut to a test's (widths of
    a test too: 40 features), and entries in its BENCHMARK.json: the
    cell runs and is correct, and not one file that was there changes."""
    root = util.make_root(tmp_path)
    base = os.path.join(root, "bench_data")
    wide = dict(shipped("configs", "epsilon-400k-d6"), rows=3000,
                features=40, heldout_rows=1024, max_depth=3, n_bins=32,
                n_summary=256)
    json.dump(wide, open(f"{base}/configs/wide.json", "w"))
    r25 = shipped("traffic", "boost-r25")
    r25["params"] = dict(r25["params"], n_trees=3, warm_trees=3,
                         check_bin_rows=256, check_heldout_rows=512,
                         check_train_rows=512)
    # three rounds at toy size learn little: the two learning limits are
    # the toy's, every other limit the shipped file's
    r25["limits"] = dict(r25["limits"], train_logloss=0.69,
                         heldout_auc={"limit": 0.55, "passes": "at_least"})
    json.dump(r25, open(f"{base}/traffic/boost-r25.json", "w"))
    shutil.copy(os.path.join(BENCH, "metrics", "round.fblock_ms.py"),
                f"{base}/metrics/round.fblock_ms.py")
    bench = json.load(open(f"{root}/BENCHMARK.json"))
    before = json.dumps(bench["workloads"])
    bench["configs"].append({"name": "wide", "source": wide["source"],
                             "file": "bench_data/configs/wide.json",
                             "reduced": [], "why": "self-test"})
    bench["workloads"].append({"name": "wide.boost-r25", "config": "wide",
                               "traffic": "boost-r25", "chips": 1,
                               "why": "self-test"})
    for m in bench["end_to_end"]:
        if m["name"] == "boost_rounds_per_s":
            m["workloads"].append("wide.boost-r25")
    bench["per_layer"].append(
        {"name": "round.fblock_ms", "unit": "ms/round", "better": "lower",
         "source": "device_trace", "layer": "kernels",
         "moves": "boost_rounds_per_s", "workloads": ["wide.boost-r25"]})
    json.dump(bench, open(f"{root}/BENCHMARK.json", "w"))
    assert json.dumps(bench["workloads"]).startswith(before[:-1])

    out, lines = run(root, "wide.boost-r25")
    assert out["correct"] is True, lines
    assert out["metrics"]["boost_rounds_per_s"]["value"] > 0
    assert set(out["compared"]) == set(r25["limits"]) | {"window.compiles",
                                                         "ops.failed"}

    # traced, on a program whose trace has no dmlc.hist.fblock scope (the
    # parent's, and the CPU's segment engine): the metric is left out
    planes = {"/device:TPU:0": {xplane.OPS_LINE: [("fusion.2", 1.0, 1.5)],
                                xplane.MODULES_LINE: [("jit_a(1)", 1.0,
                                                       1.5)]},
              "/host:CPU": {"main": [("bench.window", 0.0, 2.0),
                                     ("bench.op", 0.5, 2.0)]}}
    monkeypatch.setattr(xplane, "load", lambda path: planes)
    monkeypatch.setattr(_spans, "load", lambda path: _spans.Marks(
        [], [[("", 1.0, 1.5)]]))
    out, lines = run(root, "wide.boost-r25", trace=True)
    assert "round.fblock_ms" not in out["metrics"]
    assert "compile.cache_misses" in out["metrics"]


# -- the reader --------------------------------------------------------------------

def ctx_of(device_ops, ops=2, work=25.0):
    return test_spans.ctx_of(device_ops, [], [], ops=ops, work=work)


read = test_spans.read


def test_fblock_reader_on_a_synthetic_blocked_round():
    # a scan 1..9; level 0's six kernels with their slabs before and the
    # join after them under dmlc.hist.fblock, the pad under its own scope
    ops = [("", 1.0, 9.0),
           ("dmlc.hist.fblock", 1.0, 1.25),
           ("dmlc.hist.pad", 1.25, 1.5),
           ("dmlc.round.L0.hist", 1.5, 4.0),
           ("dmlc.hist.fblock", 4.0, 4.5),
           ("dmlc.round.L1.hist", 5.0, 8.0),
           ("dmlc.hist.fblock", 8.0, 8.25)]
    ctx = ctx_of(ops)                                  # 50 rounds
    assert read(ctx, "round.fblock_ms") == pytest.approx(1e3 * 1.0 / 50)
    # the scope is its own: the level's kernels do not count it twice
    assert read(ctx, "round.hist_ms") == pytest.approx(1e3 * 5.5 / 50)
    assert read(ctx, "round.nonhist_ms") == pytest.approx(1e3 * 2.5 / 50)


def test_fblock_reader_is_silent_without_the_scope():
    ctx = ctx_of([("", 1.0, 9.0), ("dmlc.round.L0.hist", 1.0, 4.0)])
    assert read(ctx, "round.fblock_ms") is None
    assert read(ctx_of([("", 1.0, 5.0)]), "round.fblock_ms") is None
    assert read(ctx_of([("dmlc.hist.fblock", 1.0, 2.0)], ops=0),
                "round.fblock_ms") is None


# -- the controls, at a wide shape ---------------------------------------------------

CFG = dict(util.TINY_CONFIG, rows=8000, features=300, n_bins=64,
           n_summary=512, max_depth=4)


@pytest.fixture(scope="module")
def wide():
    """The program fitted on the CPU at 8000 x 300: five columns carry
    the label, 295 are noise the splits must reject."""
    from dmlc_core_tpu.models import HistGBT

    X, y = datagen.higgs_like(CFG["rows"], CFG["features"], 35)
    model = HistGBT(n_trees=4, max_depth=CFG["max_depth"],
                    n_bins=CFG["n_bins"])
    handle = model.make_device_data(X, y)
    model.fit_device(handle)
    trees = [{k: np.asarray(v) for k, v in t.items()} for t in model.trees]
    bins_t = np.asarray(handle["bins_t"])[:, :len(y)]
    return X, y, trees, bins_t, np.asarray(model.cuts)


BOOST_NUMBERS = ["tree0.root_gain_gap", "tree0.reported_gain_gap",
                 "tree0.leaf_gap", "tree1.leaf_gap"]


def test_the_program_keeps_every_limit_of_the_wide_mix(wide):
    X, y, trees, bins_t, cuts = wide
    got = checks.boost_tree_numbers(bins_t, y, trees, CFG)
    for name in BOOST_NUMBERS:
        assert got[name] <= limit("boost-r25", name), (name, got)
    assert checks.bins_mismatches(X[:4096], bins_t[:, :4096], cuts) == \
        limit("boost-r25", "bins_mismatches")
    feats = [0, 150, 299]
    assert checks.cuts_gap(X, cuts, feats, CFG) <= limit("ingest",
                                                         "cuts_gap")
    # the splits reject the noise: every root is on a label column
    assert all(int(t["feat"][0, 0]) < 5 for t in trees)


@pytest.mark.parametrize("control, fails", [
    # (8000 rows are less than one 16,384-row tile of the bfloat16
    # accumulator: the sums are rounded once, which the leaves show and
    # the root's gain does not; at the cell's size both leave, PERF.md)
    ("bfloat16", {"tree0.leaf_gap"}),
    ("float8", {"tree1.leaf_gap"})])
def test_lower_precision_sums_and_gradients_leave_a_limit(wide, control,
                                                          fails):
    X, y, trees, bins_t, cuts = wide
    got = checks.boost_tree_numbers(
        bins_t, y, checks.control_trees(bins_t, y, trees, CFG, control), CFG)
    failed = {n for n in BOOST_NUMBERS if got[n] > limit("boost-r25", n)}
    assert fails <= failed, got


def test_bfloat16_rows_and_cuts_leave_a_limit(wide):
    X, y, trees, bins_t, cuts = wide
    k = 4096                       # the wide mix's check_bin_rows
    assert k == shipped("traffic", "boost-r25")["params"]["check_bin_rows"]
    rows16 = ref.bin_rows(X[:k], cuts, precision="bfloat16").T
    assert checks.bins_mismatches(X[:k], rows16, cuts) > \
        limit("boost-r25", "bins_mismatches")
    feats = [0, 150, 299]
    cuts16 = np.array(cuts)
    for f in feats:
        cuts16[f] = ref.quantile_cuts(X[:, f], CFG["n_bins"],
                                      CFG["n_summary"], precision="bfloat16")
    assert checks.cuts_gap(X, cuts16, feats, CFG) > limit("ingest",
                                                          "cuts_gap")


# -- the script the builder runs on the chip -----------------------------------------

def test_wide_on_chip_runs_at_toy_size():
    mod = harness.load_module(os.path.join(HERE, "wide_on_chip.py"))
    config = dict(shipped("configs", "epsilon-400k-d6"), rows=3000,
                  features=40, heldout_rows=700, max_depth=3, n_bins=32,
                  n_summary=256)
    mix = shipped("traffic", "boost-r25")
    mix["params"] = dict(mix["params"], n_trees=3, warm_trees=3,
                         check_bin_rows=256, check_heldout_rows=512,
                         check_train_rows=512)
    out = json.loads(json.dumps(mod.one_seed("wide", config, mix, 7, False)))
    assert out["rounds"] == 3 and out["features"] == 40
    for name in BOOST_NUMBERS:
        assert out["boost.program"][name] <= mix["limits"][name]
    assert out["boost.control.bfloat16"]["tree0.leaf_gap"] > \
        mix["limits"]["tree0.leaf_gap"]
    assert out["ingest.program"]["bins_mismatches"] == 0
    assert out["ingest.control.bfloat16"]["bins_mismatches"] > 0
    assert out["score"]["rows"] == 700 and len(out["score"]["call_ms"]) == 3
    assert out["score"]["score_gap"] <= 1e-5 < \
        out["score"]["score_gap.control.bfloat16"]
