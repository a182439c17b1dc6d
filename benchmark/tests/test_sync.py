"""The readers of layer ``sync`` on a hand-made four-chip trace with known
all-reduce and ``dmlc.round.L<d>.sync`` times; each reads nothing on one
chip; and the shipped ``BENCHMARK.json`` whole: every cell's
configuration file, mix, operation and every reader it lists is found by
name, as the harness finds them, and its bounds and texts are within
the contract."""

import json
import os

import pytest

from benchmark import harness, xplane
from benchmark.metrics import _spans

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SYNC_METRICS = ["psum.ms_per_round", "round.sync_ms",
                "psum.skew_ms_per_round"]
AR = "all-reduce.3 all-reduce f32[2,16,28,256]"
KERNEL = "dmlc_hist.1 custom-call/tpu_custom_call (f32[32,64,128])"

# Window 0..10 s, 100 rounds.  Every chip runs a level-0 and a level-1
# histogram kernel, each followed by its all-reduce.  Chip k finishes its
# kernels 0.01*k s late, and an all-reduce ends on every chip at once: the
# chip that arrives first waits longest.  Chip 3's second all-reduce has a
# copy under the same scope beside it.
ENDS = (2.0, 6.0)                     # where the two all-reduces end


def chip_events(k):
    """(name, scope, start, end) of chip k's operations."""
    late = 0.01 * k
    ev = []
    for level, (lo, end) in enumerate(zip((1.0, 4.0), ENDS)):
        ready = lo + 0.5 + late
        ev += [(KERNEL, f"dmlc.round.L{level}.hist", lo, ready),
               (AR, f"dmlc.round.L{level}.sync", ready, end)]
    if k == 3:
        ev.append(("copy.9 copy f32[2,16,28,256]", "dmlc.round.L1.sync",
                   6.0, 6.2))
    return ev


def ctx_of(chips, scoped=True, ops=2, work=50.0):
    events = [chip_events(k) for k in range(chips)]
    planes = {f"/device:TPU:{k}": {
        xplane.OPS_LINE: [(n, a, b) for n, _s, a, b in ev],
        xplane.MODULES_LINE: []} for k, ev in enumerate(events)}
    planes["/host:CPU"] = {"main": [("bench.window", 0.0, 10.0)]}
    ctx = harness.Ctx(root=ROOT, workload="w", config={}, mix={}, seed=0,
                      chips=chips)
    ctx.summary = xplane.summarize(planes)
    ctx.op_seconds = [1.0] * ops
    ctx.op_work = [work] * ops
    ctx.state["_spans.marks"] = _spans.Marks(
        [], [[(s if scoped else "", a, b) for _n, s, a, b in ev]
             for ev in events])
    return ctx


def read(ctx, metric):
    path = harness.find_file(ROOT, ["benchmark"], "metrics", metric + ".py")
    return harness.load_module(path).read(ctx)


def test_sync_readers_on_four_chips():
    ctx = ctx_of(4)
    # chip k's all-reduces last (0.5 - 0.01k) + (1.5 - 0.01k) s
    assert read(ctx, "psum.ms_per_round") == pytest.approx(20.0)  # chip 0
    assert read(ctx, "psum.skew_ms_per_round") == pytest.approx(
        1e3 * (2.0 - 1.94) / 100)
    # under the scopes: the all-reduces, averaged over the chips, and a
    # quarter of chip 3's copy
    assert read(ctx, "round.sync_ms") == pytest.approx(
        1e3 * ((2.0 + 1.98 + 1.96 + 1.94) / 4 + 0.2 / 4) / 100)


def test_chips_in_step_have_no_skew():
    ctx = ctx_of(4)
    for d in ctx.summary.devices:
        d.op_self_s[AR] = 1.25
    assert read(ctx, "psum.skew_ms_per_round") == pytest.approx(0.0)
    assert read(ctx, "psum.ms_per_round") == pytest.approx(12.5)


@pytest.mark.parametrize("metric", SYNC_METRICS)
def test_one_chip_reads_none(metric):
    # a one-chip round has no all-reduce; were the compiler to leave an
    # operation under the sync scope, it still is no collective
    ctx = ctx_of(1)
    ctx.summary.devices[0].op_self_s.pop(AR)
    assert read(ctx, metric) is None


def test_no_scopes_in_the_trace():
    """A program without the marks: the scope's reader returns None, the
    two that find the collective by its opcode still read it."""
    ctx = ctx_of(4, scoped=False)
    assert read(ctx, "round.sync_ms") is None
    assert read(ctx, "psum.ms_per_round") == pytest.approx(20.0)
    assert read(ctx, "psum.skew_ms_per_round") == pytest.approx(0.6)


def test_a_window_without_rounds_reads_none():
    ctx = ctx_of(4, ops=0)
    for metric in SYNC_METRICS:
        assert read(ctx, metric) is None


# -- the shipped BENCHMARK.json, whole ------------------------------------------------

def shipped():
    return harness.load_benchmark(ROOT)


@pytest.mark.parametrize("cell", [w["name"] for w in shipped()["workloads"]])
def test_every_shipped_cell_finds_its_files(cell):
    bench, entry, config, mix = harness.load_cell(ROOT, cell)
    assert int(config["chips"]) == entry["chips"]
    assert int(config["rows"]) % entry["chips"] == 0
    cfg_entry = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    assert config["source"] == cfg_entry["source"]
    assert config["reduced"] == cfg_entry["reduced"]
    assert all(key in config for key in config["reduced"])
    opmod = harness.load_module(harness.find_file(
        ROOT, bench["paths"], "ops", mix["op"] + ".py"))
    assert all(callable(getattr(opmod, f)) for f in ("setup", "op", "check"))
    # every end-to-end metric the cell reports but set-up is the mix's own
    e2e = {m["name"] for m in harness.metrics_of(bench, "end_to_end", cell)}
    assert e2e - {"setup_s"} and e2e - {"setup_s"} <= set(mix["end_to_end"])
    layers = harness.metrics_of(bench, "per_layer", cell)
    assert layers
    for m in layers:
        assert m["moves"] in e2e, m["name"]
        reader = harness.load_module(harness.find_file(
            ROOT, bench["paths"], "metrics", m["name"] + ".py"))
        assert callable(reader.read), m["name"]


def test_shipped_lists_name_cells_that_exist():
    bench = shipped()
    cells = {w["name"] for w in bench["workloads"]}
    assert {w["config"] for w in bench["workloads"]} == {
        c["name"] for c in bench["configs"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", [])) <= cells, m["name"]
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)


def test_shipped_bounds_and_texts_are_within_the_contract():
    """Every end-to-end metric has a bound a check can hold a PR to (over
    0, at most the contract's 0.1; ``setup_s`` the rule's 0.1), and every
    text the contract limits is there and fits."""
    bench = shipped()
    for m in bench["end_to_end"]:
        assert isinstance(m.get("bound"), float), m["name"]
        assert 0 < m["bound"] <= 0.1, m["name"]
    (setup,) = [m for m in bench["end_to_end"] if m["name"] == "setup_s"]
    assert setup["bound"] == 0.1
    texts = ([(w["name"], w["why"]) for w in bench["workloads"]]
             + [(c["name"], c[k]) for c in bench["configs"]
                for k in ("source", "why")])
    for name, text in texts:
        assert 0 < len(text) <= 200 and "\n" not in text, name


@pytest.mark.parametrize("metric", SYNC_METRICS)
def test_sync_entries(metric):
    (entry,) = [m for m in shipped()["per_layer"] if m["name"] == metric]
    assert entry == {"name": metric, "unit": "ms/round", "better": "lower",
                     "source": "device_trace", "layer": "sync",
                     "moves": "boost_rounds_per_s",
                     "workloads": ["higgs-d6-dp4.boost"]}
