"""What ISSUE 56 adds to the benchmark, on the CPU at toy size: the
leaf-wise configuration (LightGBM's published ``xgboost_hist`` setting on
the HIGGS table), its plain reference and check, the operation and the
seam that carry the two hyperparameters, the mix, the count of what the
builds need, five readers — and the twelfth cell, ``bosch-1m-d8.ingest-nan``,
whose files shipped with PR 42.  The shipped files load and run in a
scratch root as files only (their sizes cut); the mix's limits name every
number the check produces; the program keeps every limit that does not
depend on the size and each control leaves one; the readers read a
synthetic trace and are silent on a program without their scopes.
"""

import json
import os
import shutil

import numpy as np
import pytest

import test_spans
import util
from benchmark import (checks, checks_lossguide as cl, costs_lossguide,
                       datagen, harness, peaks, reference as ref,
                       reference_lossguide as rl, xplane)
from benchmark.metrics import _spans

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
SEED = 2**31 + 56
CONFIG = "higgs-24m-l255"
CELL = CONFIG + ".boost-r5-lossguide"
NAN_CELL = "bosch-1m-d8.ingest-nan"
NEW_READERS = ["round.expand_ms", "round.expand_nonhist_ms",
               "hist.mxu_share.lossguide", "hist.needed_row_share",
               "ingest.nan_scan_device_s"]


def shipped(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def bench_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# -- the shipped files -------------------------------------------------------------

def test_the_configuration_is_the_sources_uncut():
    cfg, flag = shipped("configs", CONFIG), shipped("configs",
                                                    "higgs-24m-d8")
    assert (cfg["grow_policy"], cfg["max_leaves"], cfg["max_depth"],
            cfg["learning_rate"], cfg["min_child_weight"],
            cfg["reg_lambda"], cfg["n_bins"], cfg["objective"],
            cfg["base_score"]) == ("lossguide", 255, 0, 0.1, 100.0, 1.0,
                                   256, "binary:logistic", 0.0)
    # the flagship's rows and rule
    assert (cfg["rows"], cfg["features"], cfg["heldout_rows"], cfg["dtype"],
            cfg["n_summary"]) == (flag["rows"], flag["features"],
                                  flag["heldout_rows"], flag["dtype"],
                                  flag["n_summary"])
    assert cfg["reduced"] == [] and cfg["chips"] == 1
    assert cfg["precision"] == flag["precision"]
    assert "byte-identical" in cfg["guarantees"]
    assert "lowest node id" in cfg["guarantees"]
    assert len(cfg["source"]) <= 200 and "Experiments.rst" in cfg["source"]
    assert any("from memory" in a for a in cfg["assumed"])
    bench = bench_json()
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry["source"] == cfg["source"] and entry["reduced"] == []
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert len({c["source"] for c in bench["configs"]}) == \
        len(bench["configs"]) == 9


def test_the_new_cells_are_entries_and_names_in_lists():
    bench = bench_json()
    cells = [w["name"] for w in bench["workloads"]]
    assert cells[-2:] == [NAN_CELL, CELL] and len(cells) == 13
    assert all(w["chips"] == 1 for w in bench["workloads"][-2:])
    for cell, e2e, emits in (
            (CELL, "boost_rounds_per_s",
             {"hist.time_share", "round.expand_ms",
              "round.expand_nonhist_ms", "hist.mxu_share.lossguide",
              "hist.needed_row_share", "setup.fit_s", "setup.ingest_s"}),
            (NAN_CELL, "ingest_rows_per_s",
             {"ingest.cuts_finite_device_s", "ingest.nan_scan_device_s",
              "ingest.cuts_device_s", "ingest.bin_device_s",
              "ingest.device_busy_share"})):
        (entry,) = [w for w in bench["workloads"] if w["name"] == cell]
        assert len(entry["why"]) <= 200
        listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
                  if cell in m.get("workloads", ())}
        assert {e2e} | emits <= listed
        assert [m["name"] for m in harness.metrics_of(
            bench, "end_to_end", cell)] == [e2e, "setup_s"]
        for m in harness.metrics_of(bench, "per_layer", cell):
            harness.find_file(ROOT, bench["paths"], "metrics",
                              m["name"] + ".py")
            assert m["moves"] in (e2e, "setup_s"), m
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    # no levels, and costs.py refuses the plan; the shipped reader of a
    # span no program opens since PR 50 gets no entry
    assert not listed & {"round.hist_ms.deepest", "dispatch.gap_ms.boost",
                         "hist.mxu_share", "round.hist_ms",
                         "round.nonhist_ms"}
    assert "ingest.idle_s.nan_scan" not in {
        m["name"] for m in bench["per_layer"]}
    assert bench["run_seconds"] == 20
    # the shipped ingest mix and operation, as they stand
    assert shipped("traffic", "ingest-nan")["op"] == "ingest_nan"


def test_the_mix_says_where_each_limit_comes_from():
    bench = bench_json()
    mix = shipped("traffic", "boost-r5-lossguide")
    assert mix["op"] == "boost_lossguide"
    assert mix["end_to_end"] == {"boost_rounds_per_s": {"kind": "rate"}}
    assert mix["params"]["n_trees"] == mix["params"]["warm_trees"] == 5
    assert set(mix["limits"]) - {"rounds_share"} <= set(mix["limits_from"])
    assert "PR 56" in mix["limits_from"]["readings"]
    for path in ("ops/boost_lossguide.py", "system_lossguide.py",
                 "checks_lossguide.py", "reference_lossguide.py",
                 "costs_lossguide.py"):
        assert os.path.isfile(os.path.join(BENCH, path))
    for reader in NEW_READERS:
        harness.find_file(ROOT, bench["paths"], "metrics", reader + ".py")


def test_the_window_is_the_accepted_operation():
    """``ops/boost_lossguide.py``'s ``op`` (the timed part) is
    ``boost.py``'s, line for line; the reference imports nothing of the
    program."""
    import inspect

    def fn(name, f):
        mod = harness.load_module(os.path.join(BENCH, "ops", name + ".py"))
        return inspect.getsource(getattr(mod, f))

    assert fn("boost_lossguide", "op") == fn("boost", "op")
    for name in ("reference_lossguide.py", "checks_lossguide.py",
                 "costs_lossguide.py"):
        assert "dmlc_core_tpu" not in open(os.path.join(BENCH, name)).read()


# -- in a scratch root, as files only ------------------------------------------------

TOY = dict(rows=40000, features=8, heldout_rows=4096, n_bins=32,
           n_summary=256, max_leaves=31, min_child_weight=20.0)


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
    root = util.make_root(tmp_path_factory.mktemp("lossguide"))
    base = os.path.join(root, "bench_data")
    cfg = dict(shipped("configs", CONFIG), **TOY)
    json.dump(cfg, open(f"{base}/configs/l31.json", "w"))
    mix = shipped("traffic", "boost-r5-lossguide")
    mix["params"] = dict(mix["params"], n_trees=3, warm_trees=3,
                         check_bin_rows=512, check_heldout_rows=4096,
                         check_train_rows=4096)
    # three rounds at eta 0.1 on 40,000 rows learn little: the toy's own
    # limits for what depends on the size, the shipped file's for the rest
    mix["limits"] = dict(
        mix["limits"], train_logloss=0.69,
        heldout_auc={"limit": 0.6, "passes": "at_least"},
        **{"tree0.min_child_hessian": {"limit": 20.0,
                                       "passes": "at_least"}})
    json.dump(mix, open(f"{base}/traffic/boost-r5-lossguide.json", "w"))
    for reader in NEW_READERS:
        shutil.copy(os.path.join(BENCH, "metrics", reader + ".py"),
                    f"{base}/metrics/{reader}.py")
    bench = json.load(open(f"{root}/BENCHMARK.json"))
    bench["configs"].append({"name": "l31", "source": cfg["source"],
                             "file": "bench_data/configs/l31.json",
                             "reduced": [], "why": "self-test"})
    cell = "l31.boost-r5-lossguide"
    bench["workloads"].append({"name": cell, "config": "l31",
                               "traffic": "boost-r5-lossguide", "chips": 1,
                               "why": "self-test"})
    for m in bench["end_to_end"]:
        if m["name"] == "boost_rounds_per_s":
            m["workloads"].append(cell)
    for m in bench_json()["per_layer"]:
        if m["name"] in NEW_READERS[:4]:
            assert m["workloads"] == [CELL]
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
    assert out["compared"]["tree0.leaves_off"]["value"] == 0
    assert out["compared"]["ops_trees_differ"]["value"] == 0
    assert any("tree 0: 31 leaves, depth" in ln for ln in lines), lines


def test_a_traced_run_reads_the_counters_and_leaves_out_what_has_no_scope(
        scratch, monkeypatch):
    """A trace without the program's scopes (the parent's, or here the
    CPU's): the two span readers return nothing and the line leaves them
    out; the two that read the check's replay and the plan give a value;
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
    got = set(out["metrics"])
    assert not {"round.expand_ms", "round.expand_nonhist_ms"} & got, lines
    assert "compile.cache_misses" in got
    share = out["metrics"]["hist.needed_row_share"]["value"]
    assert 100.0 / 31 < share < 100.0        # more than the root, not all


# -- the check and its controls ------------------------------------------------------

@pytest.fixture(scope="module")
def fitted():
    """A toy fit through the seam, and what the check reads of it."""
    from benchmark import system, system_lossguide

    cfg = dict(shipped("configs", CONFIG), **TOY)
    ctx = harness.Ctx(root=ROOT, workload="w", config=cfg, mix={},
                      seed=SEED, chips=1)
    X, y = system.training_rows(ctx)
    model = system_lossguide.new_model(ctx, 3)
    assert (model.param.grow_policy, model.param.max_leaves,
            model.param.max_depth) == ("lossguide", 31, 0)
    handle = system.ingest(model, X, y)
    model.fit_device(handle)
    bins_t = np.asarray(handle["bins_t"])[:, :len(y)]
    return cfg, bins_t, y, system.host_trees(model.trees)


def _limits():
    limits = shipped("traffic", "boost-r5-lossguide")["limits"]
    return {k: (v if isinstance(v, dict) else {"limit": v})
            for k, v in limits.items()}


def _broken(numbers, **own):
    """Names of the numbers outside the shipped limits (``own``: a toy's
    limit in a shipped one's place)."""
    out = []
    for name, value in numbers.items():
        lim = dict(_limits()[name])
        lim["limit"] = own.get(name, lim["limit"])
        ok = (value >= lim["limit"] if lim.get("passes") == "at_least"
              else value <= lim["limit"])
        if not ok:
            out.append(name)
    return sorted(out)


def test_the_program_keeps_every_limit(fitted):
    cfg, bins_t, y, trees = fitted
    numbers, facts = cl.tree_numbers(bins_t, y, trees, cfg)
    assert _broken(numbers, **{"tree0.min_child_hessian": 20.0}) == []
    assert facts["leaves"] == 31 and facts["builds"] == 31
    assert facts["rows"] == 40000 < facts["needed_rows"] < 31 * 40000
    assert facts["depth"] == rl.depth_of(trees[0]) >= 5


@pytest.mark.parametrize("precision, leaves", [
    # (a toy leaf's +-0.5 sum to small dyadics a bfloat16 sum holds: at
    # the cell's size tree0.leaf_gap leaves too, lossguide_on_chip.py)
    ("bfloat16", {"tree0.reported_gain_gap"}),
    ("float8", {"tree1.leaf_gap_by_rows"})])
def test_a_lower_precision_leaves_a_limit(fitted, precision, leaves):
    cfg, bins_t, y, trees = fitted
    control = cl.control_trees(bins_t, y, trees, cfg, precision)
    numbers, _ = cl.tree_numbers(bins_t, y, control, cfg)
    broken = set(_broken(numbers, **{"tree0.min_child_hessian": 20.0}))
    assert leaves <= broken, numbers


def test_a_swapped_expansion_order_leaves_the_budgets_rule(fitted):
    cfg, bins_t, y, trees = fitted
    swapped = [cl.swapped_order(trees[0])] + trees[1:]
    # the same tree: the same leaves over the same rows
    assert np.array_equal(
        np.sort(np.asarray(swapped[0]["value"])),
        np.sort(np.asarray(trees[0]["value"])))
    numbers, _ = cl.tree_numbers(bins_t, y, swapped, cfg)
    assert _broken(numbers, **{"tree0.min_child_hessian": 20.0}) == [
        "tree0.order_gap"]
    with pytest.raises(ValueError, match="split a child"):
        cl.swapped_order(trees[0], 0)       # expansion 1 splits a child of 0


def test_a_depth_wise_tree_under_the_same_leaf_count_is_rejected(fitted):
    from benchmark import system

    cfg, bins_t, y, _ = fitted
    ctx = harness.Ctx(root=ROOT, workload="w", mix={}, seed=SEED, chips=1,
                      config=dict(cfg, max_depth=5))
    X, _ = system.training_rows(ctx)
    model = system.new_model(ctx, 2)         # the seam without the keys
    assert model.param.grow_policy == "depthwise"
    model.fit_device(system.ingest(model, X, y))
    trees = [rl.from_levels(t, cfg["n_bins"])
             for t in system.host_trees(model.trees)]
    assert len(rl.leaves_of(trees[0])) == 32
    numbers, _ = cl.tree_numbers(bins_t, y, trees, cfg)
    broken = _broken(numbers, **{"tree0.min_child_hessian": 20.0})
    assert {"tree0.leaves_off", "tree0.order_gap"} <= set(broken)
    assert numbers["tree0.leaf_gap"] < 1e-5   # its leaves are sound


def test_a_leaf_under_min_child_weight_is_rejected(fitted):
    cfg, bins_t, y, trees = fitted
    numbers, _ = cl.tree_numbers(bins_t, y, trees, cfg)
    assert 20.0 <= numbers["tree0.min_child_hessian"] < 100.0
    # the shipped limit is the configuration's 100: this tree, grown
    # under 20, breaks it and nothing else
    assert _broken(numbers) == ["tree0.min_child_hessian"]


def test_the_reference_grows_the_programs_first_tree(fitted):
    cfg, bins_t, y, trees = fitted
    g, h = ref.logistic_grad_hess(np.zeros(len(y)), y.astype(np.float64))
    want = rl.grow(bins_t, g, h, cfg["n_bins"], cfg["max_leaves"],
                   cfg["reg_lambda"], cfg["min_child_weight"],
                   cfg["learning_rate"])
    for k in ("left", "right", "feat", "thr"):
        assert np.array_equal(trees[0][k], want[k]), k
    np.testing.assert_allclose(trees[0]["value"], want["value"], rtol=1e-5,
                               atol=1e-8)


# -- the count and the readers ---------------------------------------------------------

def hand_made_tree():
    """Three expansions over 100 rows of one feature: 0 -> (1, 2) at bin
    <= 5, then 2 -> (3, 4) at bin <= 7, then 1 -> (5, 6) at bin <= 1."""
    t = rl.empty_tree(4, 16)
    for i, (thr, lc) in {0: (5, 1), 2: (7, 3), 1: (1, 5)}.items():
        t["thr"][i], t["left"][i], t["right"][i] = thr, lc, lc + 1
        t["gain"][i] = 1.0
    bins_t = (np.arange(100) % 10).astype(np.uint8)[None, :]
    return t, bins_t


def test_costs_on_a_hand_made_tree():
    t, bins_t = hand_made_tree()
    g = np.ones(100)
    rep = rl.replay(bins_t, g, g, t, 16, 1.0, 0.0)
    # rows: root 100; 0 -> 60 | 40; 2 -> 20 | 20; 1 -> 20 | 40
    assert [int(rep["rows"][i]) for i in range(7)] == [
        100, 60, 40, 20, 20, 20, 40]
    assert rep["order"] == [0, 2, 1] and rl.depth_of(t) == 2
    needed = rl.needed_rows(rep, t)
    assert needed == 100 + 40 + 20 + 20
    assert costs_lossguide.hist_mxu_flops_per_tree(needed, 1, 16) == \
        2 * 32 * 1 * 180
    assert costs_lossguide.needed_row_share(needed, 100, 4) == \
        pytest.approx(0.45)
    # depth-wise's count at one node a build is the same arithmetic
    from benchmark import costs
    assert costs_lossguide.hist_mxu_flops_per_tree(1000, 28, 256) == \
        costs.hist_mxu_flops_per_round(1000, 28, 256, 1, {})


read = test_spans.read


def _ctx(device_ops, **counters):
    ctx = test_spans.ctx_of(device_ops, [], [], ops=2, work=5.0)
    ctx.counters.update(counters)
    ctx.config = {"features": 28, "n_bins": 256}
    ctx.device_kind = "TPU v5 lite"
    return ctx


def test_readers_on_a_synthetic_leafwise_round():
    # 10 rounds (two fits of 5) of 4 expansions: a scan 1..9 whose body
    # holds picks (1 s), builds (4 s, 0.5 of it the kernel's own pad) and
    # settles (2 s); the root before it
    ops = [("dmlc.round.root", 0.5, 1.0), ("", 1.0, 9.0),
           ("dmlc.round.expand.pick", 1.0, 2.0),
           ("dmlc.hist.pad", 2.0, 2.5),
           ("dmlc.round.expand.hist", 2.5, 6.0),
           ("dmlc.round.expand.settle", 6.0, 8.0)]
    plan = {"expansions": 4, "hist_rows_per_build": 1000}
    ctx = _ctx(ops, round_plan=plan, **{"lossguide.needed_rows": 2000,
                                        "lossguide.builds": 5})
    assert read(ctx, "round.expand_ms") == pytest.approx(1e3 * 7.0 / 40)
    assert read(ctx, "round.expand_nonhist_ms") == pytest.approx(
        1e3 * 3.0 / 10)
    assert read(ctx, "hist.needed_row_share") == pytest.approx(40.0)
    # a depth-wise program: neither the scopes nor the counts
    other = _ctx(test_spans.DEVICE_OPS, round_plan={"grow_policy":
                                                    "depthwise"})
    for name in NEW_READERS[:4]:
        assert read(other, name) is None


def test_the_share_of_the_peak_counts_what_the_builds_need():
    ctx = _ctx([], **{"lossguide.needed_rows": 4_000_000})
    # 2 s in the kernels over 10 trees
    ctx.summary = xplane.summarize({
        "/device:TPU:0": {xplane.OPS_LINE: [
            ("h custom-call/tpu_custom_call f32[8]", 1.0, 3.0)],
            xplane.MODULES_LINE: []},
        "/host:CPU": {"main": [("bench.window", 0.0, 10.0)]}})
    flops = 2 * 512 * 28 * 4_000_000 * 10
    assert read(ctx, "hist.mxu_share.lossguide") == pytest.approx(
        100.0 * flops / 2.0 / 197e12)
    del ctx.counters["lossguide.needed_rows"]
    assert read(ctx, "hist.mxu_share.lossguide") is None


def test_nan_scan_reader_on_a_synthetic_ingest():
    ops = [("dmlc.cuts.nan_scan", 1.0, 1.25), ("dmlc.cuts.finite", 1.25, 3.0),
           ("dmlc.cuts.nan_scan", 6.0, 6.25), ("dmlc.cuts.finite", 6.25, 8.0)]
    ctx = test_spans.ctx_of(ops, [], [], ops=2)
    assert read(ctx, "ingest.nan_scan_device_s") == pytest.approx(0.25)
    assert read(ctx, "ingest.cuts_finite_device_s") == pytest.approx(1.75)
    dense = test_spans.ctx_of([("dmlc.cuts", 1.0, 3.0)], [], [], ops=1)
    assert read(dense, "ingest.nan_scan_device_s") is None
