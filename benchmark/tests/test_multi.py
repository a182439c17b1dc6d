"""What ISSUE 48 adds to the benchmark, on the CPU at toy size: the
multiclass configuration (Covertype under ``multi:softmax``), its data
rule, its plain reference (a softmax row by row), an operation with the
accepted boost window that builds its model before it draws a row, a mix
whose limits are the cell's own, and two readers.  The shipped files load
and run in a scratch root as files only (their sizes cut), through
``harness.main``; the mix's limits name every number the operation's
check produces; the program keeps every limit that does not depend on the
size and each control leaves one; the readers read a synthetic trace and
are silent on a program without their scope or plan key.

As in ``test_rank.py``, membership in ``BENCHMARK.json``'s lists is
asserted with ``<=``, never ``==``: the file is append-only and a later
PR may put this cell on more lists.
"""

import io
import json
import os
import shutil
from contextlib import redirect_stdout

import numpy as np
import pytest

import test_spans
import util
from benchmark import (checks, checks_multi, datagen_multi, harness,
                       reference as ref, reference_multi as rm, xplane)
from benchmark.metrics import _spans

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
SEED = 2**31 + 48
CONFIG = "covtype-7m-d6"
MIX = "boost-r25-multi"
CELL = CONFIG + "." + MIX
NEW_READERS = ["hist.mxu_share.multi", "round.update_ms"]
K = 7


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

def test_the_configuration_is_the_sources_shape_scaled_and_says_so():
    cfg, deep = shipped("configs", CONFIG), shipped("configs",
                                                    "higgs-24m-d8")
    assert cfg["rows"] % 581_012 == 0 and 12 <= cfg["rows"] // 581_012 <= 16
    assert (cfg["features"], cfg["classes"]) == (54, K)
    assert (cfg["objective"], cfg["max_depth"], cfg["n_bins"],
            cfg["learning_rate"], cfg["reg_lambda"],
            cfg["min_child_weight"], cfg["base_score"]) == \
        ("multi:softmax", 6, 256, 0.3, 1.0, 1.0, 0.0)
    assert "num_class" not in cfg              # the model learns it
    assert cfg["reduced"] == [] and cfg["chips"] == 1
    assert cfg["architecture"] is None
    assert f"x{cfg['rows'] // 581_012}" in cfg["scaled"]
    assert f"{cfg['rows']:,}" in cfg["scaled"]
    assert cfg["n_summary"] == 8 * cfg["n_bins"]
    for promise in ("equal to a cut", "one tree for every class",
                    "one softmax a row", "byte-identical", "column c"):
        assert promise in cfg["guarantees"], promise
    assert cfg["precision"].endswith(deep["precision"].split("; ", 1)[1])
    assert len(cfg["source"]) <= 200
    for word in ("Covertype", "581,012 x 54", "cover_type.py",
                 f"rows x{cfg['rows'] // 581_012}"):
        assert word in cfg["source"], word
    assert any("GiB" in a and "warm" in a for a in cfg["assumed"])
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
        "round.grad_ms", "hist.time_share", "setup.fit_s", "setup.ingest_s",
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
    # costs.py counts ONE tree a round: the flagship's share would read a
    # seventh here; a window holds one dispatch, so no gap between two
    assert not {"hist.mxu_share", "dispatch.gap_ms.boost"} & listed
    assert [m["name"] for m in harness.metrics_of(bench, "end_to_end",
                                                  CELL)] == [e2e, "setup_s"]
    for m in harness.metrics_of(bench, "per_layer", CELL):
        harness.find_file(ROOT, bench["paths"], "metrics", m["name"] + ".py")
        assert m["moves"] in (e2e, "setup_s"), m
    new = {m["name"]: m for m in bench["per_layer"]
           if m["name"] in NEW_READERS}
    assert [m["name"] for m in bench["per_layer"]
            if m["name"] in NEW_READERS] == NEW_READERS     # in this order
    assert (new[NEW_READERS[0]]["layer"], new[NEW_READERS[0]]["unit"],
            new[NEW_READERS[0]]["better"]) == ("kernels", "%", "higher")
    assert (new[NEW_READERS[1]]["layer"], new[NEW_READERS[1]]["better"]) == \
        ("objective", "lower")
    for name in NEW_READERS:
        assert new[name]["moves"] == e2e and CELL in new[name]["workloads"]
    assert bench["run_seconds"] == 20
    cells = bench["workloads"]
    assert [w["name"] for w in cells].index(CELL) == 9   # after the nine
    assert sum(w["chips"] == 4 for w in cells) <= len(cells) // 4


def test_the_mix_says_where_each_limit_comes_from():
    bench = bench_json()
    mix = shipped("traffic", MIX)
    assert mix["op"] == "boost_multi"
    assert mix["end_to_end"] == {"boost_rounds_per_s": {"kind": "rate"}}
    assert mix["params"] == {
        "n_trees": 25, "warm_trees": 25, "check_bin_rows": 4096,
        "check_heldout_rows": 65536, "check_train_rows": 65536}
    assert mix["trace_seconds"] == 10
    assert set(mix["limits"]) - {"rounds_share", "rows_share"} <= \
        set(mix["limits_from"])
    assert "PR 48" in mix["limits_from"]["readings"]
    harness.find_file(ROOT, bench["paths"], "ops", "boost_multi.py")


def test_the_window_is_the_accepted_operation():
    """``ops/boost_multi.py`` is ``ops/boost.py`` but for where the rows
    come from and what the check compares: ``op`` (the timed part) is the
    same source, line for line; set-up builds the model BEFORE it draws a
    row, so a program that cannot run the cell fails at once."""
    import inspect

    def src(name, f=None):
        mod = harness.load_module(os.path.join(BENCH, "ops", name + ".py"))
        return inspect.getsource(getattr(mod, f) if f else mod)

    assert src("boost_multi", "op") == src("boost", "op")
    whole = src("boost_multi")
    assert "num_class" not in whole.split('"""', 2)[2]   # learned, not given
    assert ".fit(" not in whole.replace("model.fit_device(", "")
    setup = src("boost_multi", "setup")
    for a, b in zip(("system.new_model(", "_rows(", "system.ingest(",
                     'p["warm_trees"]', "model.fit_device(handle)"),
                    ("_rows(", "system.ingest(", 'p["warm_trees"]',
                     "model.fit_device(handle)", 'p["n_trees"])\n    ctx')):
        assert setup.index(a) < setup.index(b), (a, b)


# -- in a scratch root, as files only, through harness.main ---------------------------

TOY = dict(rows=12000, n_bins=32, n_summary=256, max_depth=3)


@pytest.fixture(scope="module")
def scratch(tmp_path_factory):
    root = util.make_root(tmp_path_factory.mktemp("multi"))
    base = os.path.join(root, "bench_data")
    cfg = dict(shipped("configs", CONFIG), **TOY)
    json.dump(cfg, open(f"{base}/configs/cov.json", "w"))
    mix = shipped("traffic", MIX)
    mix["params"] = dict(mix["params"], n_trees=3, warm_trees=3,
                         check_bin_rows=512, check_heldout_rows=2048,
                         check_train_rows=2048)
    # three rounds on 12,000 rows learn little: the toy's own limits for
    # what depends on the size, the shipped file's for the rest
    mix["limits"] = dict(mix["limits"], train_mlogloss=1.5,
                         heldout_merror=0.6)
    json.dump(mix, open(f"{base}/traffic/{MIX}.json", "w"))
    for reader in NEW_READERS:
        shutil.copy(os.path.join(BENCH, "metrics", reader + ".py"),
                    f"{base}/metrics/{reader}.py")
    bench = json.load(open(f"{root}/BENCHMARK.json"))
    bench["configs"].append({"name": "cov", "source": cfg["source"],
                             "file": "bench_data/configs/cov.json",
                             "reduced": [], "why": "self-test"})
    bench["workloads"].append({"name": "cov." + MIX, "config": "cov",
                               "traffic": MIX, "chips": 1,
                               "why": "self-test"})
    for m in bench["end_to_end"]:
        if m["name"] == "boost_rounds_per_s":
            m["workloads"].append("cov." + MIX)
    shipped_entries = {m["name"]: m for m in bench_json()["per_layer"]}
    for reader in NEW_READERS:
        bench["per_layer"].append(dict(shipped_entries[reader],
                                       workloads=["cov." + MIX]))
    json.dump(bench, open(f"{root}/BENCHMARK.json", "w"))
    return root, mix


def main_line(root, monkeypatch, trace=0):
    """One run through ``harness.main`` (which refuses a CPU: the claim
    is let through here), its result line parsed."""
    real = harness.claim_devices
    monkeypatch.setattr(harness, "claim_devices",
                        lambda chips, require_chip: real(chips, False))
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = harness.main(["--workload", "cov." + MIX, "--seed", str(SEED),
                           "--seconds", "0.3", "--trace", str(trace)],
                          root=root)
    lines = buf.getvalue().splitlines()
    assert rc == 0, lines
    return json.loads(lines[-1]), lines


def test_new_files_run_in_a_scratch_root(scratch, monkeypatch):
    root, mix = scratch
    out, lines = main_line(root, monkeypatch)
    assert out["correct"] is True, lines
    assert out["metrics"]["boost_rounds_per_s"]["value"] > 0
    assert set(out["metrics"]) == {"boost_rounds_per_s", "setup_s"}
    assert set(out["compared"]) == set(mix["limits"]) | {
        "window.compiles", "ops.failed"}
    assert out["compared"]["rows_share"]["value"] == 1.0
    assert out["compared"]["ops_trees_differ"]["value"] == 0
    assert out["compared"]["bins_mismatches"]["value"] == 0
    assert any("worst leaf" in ln for ln in lines)


def test_a_traced_run_without_the_marks_leaves_the_new_metrics_out(
        scratch, monkeypatch):
    """A trace without kernels or the update's scope: both readers return
    nothing, the line leaves the metrics out, nothing raises."""
    root, _ = scratch
    planes = {"/device:TPU:0": {xplane.OPS_LINE: [("fusion.2", 1.0, 1.5)],
                                xplane.MODULES_LINE: [("jit_a(1)", 1.0,
                                                       1.5)]},
              "/host:CPU": {"main": [("bench.window", 0.0, 2.0),
                                     ("bench.op", 0.5, 2.0)]}}
    monkeypatch.setattr(xplane, "load", lambda path: planes)
    monkeypatch.setattr(_spans, "load", lambda path: _spans.Marks(
        [], [[("", 1.0, 1.5)]]))
    out, lines = main_line(root, monkeypatch, trace=1)
    assert not set(NEW_READERS) & set(out["metrics"]), lines
    assert "compile.cache_misses" in out["metrics"]


# -- the readers ---------------------------------------------------------------------

read = test_spans.read
KERNEL = "dmlc_hist.1 custom-call/tpu_custom_call (f32[32,64,128])"


def test_the_multiclass_mxu_share_counts_every_tree_of_a_round():
    from benchmark import costs, peaks

    cfg = dict(rows=1_000_000, features=54, n_bins=256, max_depth=6)
    ctx = test_spans.ctx_of([(KERNEL, 1.0, 5.0), ("fusion.3", 5.0, 6.0)],
                            [], [], ops=1, work=25.0)
    ctx.config, ctx.device_kind = cfg, "TPU v5 lite"
    assert read(ctx, "hist.mxu_share.multi") is None        # no plan
    ctx.counters["round_plan"] = {"grow_policy": "depthwise",
                                  "bin_layout": None}
    assert read(ctx, "hist.mxu_share.multi") is None        # the parent's plan
    one = read(ctx, "hist.mxu_share")
    ctx.counters["round_plan"]["trees_per_round"] = K
    flops = costs.hist_mxu_flops_per_round(1_000_000, 54, 256, 6,
                                           ctx.counters["round_plan"])
    want = 100.0 * flops * K * 25 / 4.0 / peaks.peak("TPU v5 lite")[
        "bf16_flops"]
    assert read(ctx, "hist.mxu_share.multi") == pytest.approx(want)
    assert read(ctx, "hist.mxu_share.multi") == pytest.approx(K * one)
    assert want < 105.0


def test_the_update_reader_reads_its_scope_alone():
    ops = [("", 1.0, 9.0), ("dmlc.round.grad", 1.0, 1.5),
           ("dmlc.round.class", 1.5, 2.0), ("dmlc.round.L0.hist", 2.0, 7.0),
           ("dmlc.round.update", 7.0, 7.25)]
    ctx = test_spans.ctx_of(ops, [], [], ops=1, work=25.0)
    assert read(ctx, "round.update_ms") == pytest.approx(10.0)
    assert read(ctx, "round.grad_ms") == pytest.approx(20.0)
    # the class loop's own slices are inside what the kernels leave
    assert read(ctx, "round.nonhist_ms") >= (read(ctx, "round.update_ms")
                                             + read(ctx, "round.grad_ms"))
    none = test_spans.ctx_of([("dmlc.round.L0.hist", 1.0, 4.0)], [], [])
    assert read(none, "round.update_ms") is None


# -- the data rule ---------------------------------------------------------------------

def test_the_rows_are_covertypes_shape_and_the_seeds(monkeypatch):
    a = datagen_multi.covtype_like(600_000, SEED)
    monkeypatch.setattr(datagen_multi, "_THREADS", 1)
    b = datagen_multi.covtype_like(600_000, SEED)
    for u, v in zip(a, b):
        assert np.array_equal(u, v)
    X, y = a
    assert X.shape == (600_000, 54) and X.dtype == np.float32
    assert (X == np.rint(X)).all()                     # whole numbers
    assert (X[:, datagen_multi.WILDERNESS].sum(axis=1) == 1).all()
    assert (X[:, datagen_multi.SOIL].sum(axis=1) == 1).all()
    assert set(np.unique(X[:, 10:])) == {0.0, 1.0}
    for col in (datagen_multi.SHADE_9AM, datagen_multi.SHADE_NOON,
                datagen_multi.SHADE_3PM):
        assert 0 <= X[:, col].min() and X[:, col].max() <= 254
    assert X[:, datagen_multi.ELEVATION].min() >= 1859
    assert X[:, datagen_multi.ELEVATION].max() <= 3858
    share = np.bincount(y.astype(int), minlength=K) / len(y)
    assert np.abs(share - np.array(datagen_multi.CLASS_SHARES)).max() < 2e-3
    assert sum(datagen_multi.CLASS_ROWS) == 581_012
    # the label reads the elevation band, the area and the soil group
    elev = X[:, datagen_multi.ELEVATION]
    assert elev[y == 6].mean() > elev[y == 0].mean() > elev[y == 2].mean()
    assert X[y == 3, 13].mean() == 1.0 and X[y == 0, 13].mean() == 0.0
    group = X[:, datagen_multi.SOIL].argmax(axis=1) // 5
    assert group[y == 6].mean() > group[y == 2].mean() + 3
    # held-out rows and another seed: other rows of the same rule
    Xh, yh = datagen_multi.covtype_like(4096, SEED, stream=1)
    assert not np.array_equal(Xh, X[:4096])
    assert not np.array_equal(datagen_multi.covtype_like(4096, 7)[0],
                              X[:4096])
    with pytest.raises(ValueError):
        datagen_multi.covtype_like(16, SEED, features=28)


# -- the reference ---------------------------------------------------------------------

def test_the_reference_is_the_softmax_row_by_row():
    rng = np.random.default_rng(5)
    m = rng.normal(size=(9, K))
    y = rng.integers(0, K, 9)
    g, h = rm.softmax_grad_hess(m, y)
    for i in range(9):
        p = np.exp(m[i]) / np.exp(m[i]).sum()
        for c in range(K):
            assert g[i, c] == pytest.approx(p[c] - (y[i] == c), abs=1e-15)
            assert h[i, c] == pytest.approx(
                max(2 * p[c] * (1 - p[c]), 1e-6), abs=1e-15)
    assert np.allclose(g.sum(axis=1), 0.0, atol=1e-15)  # one softmax a row
    g1, h1 = rm.softmax_grad_hess(m, y, control="hess1")
    assert np.array_equal(g1, g) and np.allclose(2 * h1, h)
    go, _ = rm.softmax_grad_hess(m, y, control="ovr")
    assert np.abs(go.sum(axis=1)).max() > 0.1           # uncoupled
    assert rm.mlogloss(np.zeros((4, K)), np.arange(4)) == pytest.approx(
        np.log(K))
    assert rm.merror(np.eye(K), np.arange(K)) == 0.0
    # the counts of the round-0 histograms are reference.py's own
    bins_t = rng.integers(0, 16, (3, 500)).astype(np.uint8)
    yy = rng.integers(0, K, 500)
    counts = rm.class_bin_counts(bins_t, yy, K, 16)
    g_of, h_of = rm.softmax_grad_hess(np.zeros((K, K)), np.arange(K))
    gg, hh = rm.softmax_grad_hess(np.zeros((500, K)), yy)
    G, H = ref.root_histogram(bins_t, gg[:, 2], hh[:, 2], 16)
    G2, H2 = checks_multi._round0_histograms(counts, g_of, h_of, 2)
    assert np.allclose(G, G2, atol=1e-12) and np.allclose(H, H2, atol=1e-12)


# -- the program and the controls ----------------------------------------------------

CFG = dict(shipped("configs", CONFIG), rows=40000, n_bins=64, n_summary=512,
           max_depth=4)


@pytest.fixture(scope="module")
def fitted():
    from dmlc_core_tpu.models import HistGBT

    X, y = datagen_multi.covtype_like(CFG["rows"], SEED)
    model = HistGBT(n_trees=12, max_depth=CFG["max_depth"],
                    n_bins=CFG["n_bins"], learning_rate=CFG["learning_rate"],
                    objective="multi:softmax")
    handle = model.make_device_data(X, y)
    model.fit_device(handle)
    trees = [{k: np.asarray(v) for k, v in t.items()} for t in model.trees]
    bins_t = np.asarray(handle["bins_t"])[:, :len(y)]
    return X, y, trees, bins_t, np.asarray(model.cuts), model


def test_the_program_keeps_the_limits_that_no_size_moves(fitted):
    X, y, trees, bins_t, cuts, model = fitted
    assert model.param.num_class == K and model.round_plan[
        "trees_per_round"] == K
    got = checks_multi.boost_tree_numbers(bins_t, y, trees, CFG)
    assert set(got) | {"rounds_share", "rows_share", "bins_mismatches",
                       "ops_trees_differ", "score_gap", "train_mlogloss",
                       "heldout_merror"} == \
        set(shipped("traffic", MIX)["limits"])
    # the CPU feeds float32 gradients to a float32 histogram: the root's
    # split and gain are rounding; the leaves are judged by the rows
    assert got["tree0.root_gain_gap"] <= limit("tree0.root_gain_gap")
    assert got["tree0.reported_gain_gap"] <= min(
        limit("tree0.reported_gain_gap"), 1e-3)
    for name in ("tree0.leaf_gap_by_rows", "tree1.leaf_gap_by_rows"):
        assert got[name] <= limit(name), (name, got)
    assert checks.bins_mismatches(X[:4096], bins_t[:, :4096], cuts) == 0
    Xh, _ = datagen_multi.covtype_like(2048, SEED, stream=1)
    margin = model.predict(Xh, output_margin=True)
    assert margin.shape == (2048, K)
    assert checks_multi.score_gap(Xh, margin, cuts, trees, CFG) <= \
        limit("score_gap")
    assert checks_multi.score_gap(Xh, margin[:, :3], cuts, trees,
                                  CFG) == float("inf")


@pytest.mark.parametrize("control, fails", [
    ("ovr", "tree0.leaf_gap_by_rows"), ("hess1", "tree0.leaf_gap_by_rows"),
    ("bfloat16", "tree0.leaf_gap_by_rows"),
    ("float8", "tree0.reported_gain_gap"),
    ("shifted", "tree1.leaf_gap_by_rows")])
def test_each_control_leaves_a_limit(fitted, control, fails, monkeypatch):
    X, y, trees, bins_t, cuts, _model = fitted
    # a bfloat16 running sum is rounded once a 16,384-row tile: 568 times
    # at the cell's size, thrice at the toy's — cut the tile to keep the
    # roundings (the control reads 0.65 on the chip: PERF.md section 2)
    monkeypatch.setattr(ref, "_BF16_TILE", 256)
    got = checks_multi.boost_tree_numbers(
        bins_t, y, checks_multi.control_trees(bins_t, y, trees, CFG,
                                              control), CFG)
    assert got[fails] > limit(fails), got


@pytest.mark.parametrize("fault", [dict(precision="bfloat16"),
                                   dict(shift=1)],
                         ids=["bf16_margin", "shifted"])
def test_the_one_predict_is_held_against_a_faulty_descent(fitted, fault):
    X, y, trees, bins_t, cuts, model = fitted
    Xh, _ = datagen_multi.covtype_like(2048, SEED, stream=1)
    margin = model.predict(Xh, output_margin=True)
    assert checks_multi.score_gap(Xh, margin, cuts, trees, CFG,
                                  **fault) > limit("score_gap")


def test_a_fit_stopped_early_shows_in_what_the_ensemble_learns(fitted):
    X, y, trees, bins_t, cuts, _model = fitted
    Xh, yh = datagen_multi.covtype_like(4096, SEED, stream=1)

    def learn(some):
        return checks_multi.learning_numbers(X[:4096], y[:4096], Xh, yh,
                                             cuts, some, CFG)

    whole, short = learn(trees), learn(trees[:6])
    assert whole["train_mlogloss"] < short["train_mlogloss"] < np.log(K)
    assert whole["heldout_merror"] < 0.4
    # what heldout_merror is held against: a model read onto other columns
    assert rm.merror(rm.ensemble_margin(Xh, cuts, trees, 0.0, shift=1),
                     yh) > 0.8
    assert checks.trees_differ(trees, trees) == 0
