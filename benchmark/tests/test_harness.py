"""The harness, end to end at toy size on the CPU: cells, mixes and
per-layer metrics are found by name among files a PR may add; the result
object has the contract's keys and no others; a run without a chip is
refused; a timed path broken underneath comes out as not correct."""

import io
import json
from contextlib import redirect_stdout

import numpy as np
import pytest

import util
from benchmark import harness, xplane

SEED = 2**31 + 77
KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


def run(root, cell, trace=False, seconds=0.3):
    lines = []
    out = harness.run_cell(root, cell, SEED, seconds, trace,
                           require_chip=False, say=lines.append)
    return out, lines


@pytest.mark.parametrize("mix, metric", [
    ("tiny-boost", "boost_rounds_per_s"),
    ("tiny-ingest", "ingest_rows_per_s"),
    ("tiny-score", "score_p95_ms"),
])
def test_cells_run_and_are_correct(tmp_path, mix, metric):
    out, lines = run(util.make_root(tmp_path), "tiny." + mix)
    assert out["correct"] is True, lines
    assert set(json.loads(json.dumps(out))) == KEYS
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert out["metrics"][metric]["value"] > 0
    assert out["metrics"]["setup_s"]["unit"] == "s"
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    # every number compared is printed beside its limit, and is the
    # result line's last key
    assert any(ln.startswith("[correct] ") and "at_most" in ln
               for ln in lines)
    assert list(out)[-1] == "compared" and out["compared"]
    assert all(set(c) == {"value", "limit", "passes", "ok"}
               for c in out["compared"].values())


def test_the_score_cell_keeps_only_what_its_check_reads(tmp_path, monkeypatch):
    """However many calls a window holds, the operation keeps the first
    lap and a seeded sample of them: holding every answer slowed the calls
    it was timing (PERF.md section 2, PR 32)."""
    from benchmark import checks

    seen = {}
    real = checks.apply_limits

    def spy(ctx, numbers):
        seen["state"] = ctx.state
        return real(ctx, numbers)

    monkeypatch.setattr(checks, "apply_limits", spy)
    out, lines = run(util.make_root(tmp_path), "tiny.tiny-score")
    st = seen["state"]
    assert out["correct"] is True, lines
    assert st["calls"] == out["attempted"] > st["n_slabs"] + st["want"]
    assert len(st["first_lap"]) == st["n_slabs"]
    assert len({i for i, _o in st["sample"]}) == len(st["sample"]) == st["want"]
    assert all(len(o) == st["rows"] for _i, o in st["sample"])


def fake_trace(monkeypatch):
    """A traced run on the CPU has no device plane: hand the reduction a
    synthetic one and let everything else run."""
    planes = {
        "/device:TPU:0": {
            xplane.OPS_LINE: [("sort.1", 1.0, 2.0), ("fusion.2", 3.0, 3.5)],
            xplane.MODULES_LINE: [("jit_a(1)", 1.0, 2.0),
                                  ("jit_b(2)", 3.0, 3.5)]},
        "/host:CPU": {"main": [("bench.window", 0.0, 4.0),
                               ("bench.op", 0.5, 2.5),
                               ("bench.op", 2.5, 4.0)]},
    }
    monkeypatch.setattr(xplane, "load", lambda path: planes)


def test_new_config_mix_and_metric_are_files_only(tmp_path, monkeypatch):
    """A later PR's additions: a configuration file, a traffic mix that is
    parameters alone, a per-layer metric reader — new files, new entries
    in BENCHMARK.json, and not one edit to a file that is there."""
    metric = ("def read(ctx):\n"
              "    return ctx.summary.op_seconds(lambda n: 'sort' in n)\n")
    absent = "def read(ctx):\n    return None\n"
    root = util.make_root(tmp_path, per_layer=[
        {"name": "new.sort_s", "unit": "s", "better": "lower",
         "source": "device_trace", "layer": "ingest",
         "moves": "ingest_rows_per_s"},
        {"name": "new.absent", "unit": "s", "better": "lower",
         "source": "device_trace", "layer": "ingest",
         "moves": "ingest_rows_per_s"}],
        extra_files={"metrics/new.sort_s.py": metric,
                     "metrics/new.absent.py": absent})
    # the new configuration and mix, written after the root was made
    bench = json.load(open(f"{root}/BENCHMARK.json"))
    cfg = dict(util.TINY_CONFIG, rows=3000)
    json.dump(cfg, open(f"{root}/bench_data/configs/tiny-3k.json", "w"))
    mix = dict(util.TINY_MIXES["tiny-ingest"],
               params={"check_features": 1, "check_bin_rows": 512})
    json.dump(mix, open(f"{root}/bench_data/traffic/ingest-small.json", "w"))
    bench["configs"].append({"name": "tiny-3k", "source": "self-test",
                             "file": "bench_data/configs/tiny-3k.json",
                             "reduced": [], "why": "self-test"})
    bench["workloads"].append({"name": "tiny-3k.ingest-small",
                               "config": "tiny-3k",
                               "traffic": "ingest-small", "chips": 1,
                               "why": "self-test"})
    for m in bench["end_to_end"]:
        if m["name"] == "ingest_rows_per_s":
            m["workloads"].append("tiny-3k.ingest-small")
    json.dump(bench, open(f"{root}/BENCHMARK.json", "w"))

    out, lines = run(root, "tiny-3k.ingest-small")
    assert out["correct"] is True, lines
    assert out["metrics"]["ingest_rows_per_s"]["value"] > 0

    fake_trace(monkeypatch)
    out, lines = run(root, "tiny-3k.ingest-small", trace=True)
    assert set(out) == KEYS | {"breakdown"}
    assert out["metrics"]["new.sort_s"] == {"value": 1.0, "unit": "s"}
    assert "new.absent" not in out["metrics"]     # nothing to read: left out
    assert "compile.cache_misses" in out["metrics"]
    assert out["device"]["busy_s"] == pytest.approx(1.5)
    assert out["device"]["window_s"] == pytest.approx(4.0)
    assert out["breakdown"]["device_ops"][0] == ["sort.1", 1.0]
    assert len(out["breakdown"]["idle_gaps"]) <= 10


def test_refuses_without_a_chip(tmp_path):
    """No accelerator: another exit code than 0 and no result line."""
    root = util.make_root(tmp_path)
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = harness.main(["--workload", "tiny.tiny-score", "--seed", "1",
                           "--seconds", "1", "--trace", "0"], root=root)
    assert rc != 0
    assert not any(ln.startswith("{") for ln in buf.getvalue().splitlines())


def test_unknown_cell_and_too_few_chips_are_refused(tmp_path):
    root = util.make_root(tmp_path)
    with pytest.raises(harness.Refused):
        harness.run_cell(root, "tiny.nothing", 1, 1, False,
                         require_chip=False)
    bench = json.load(open(f"{root}/BENCHMARK.json"))
    bench["workloads"][0]["chips"] = 4
    json.dump(bench, open(f"{root}/BENCHMARK.json", "w"))
    with pytest.raises(harness.Refused):
        harness.run_cell(root, bench["workloads"][0]["name"], 1, 1, False,
                         require_chip=False)


# -- the timed path broken underneath ------------------------------------------------

def test_a_scoring_answer_altered_where_it_is_produced(tmp_path, monkeypatch):
    from dmlc_core_tpu.models import HistGBT

    real = HistGBT.predict

    def off_by_a_little(self, X, *a, **kw):
        out = np.array(real(self, X, *a, **kw))
        out[len(out) // 2] += 1e-3               # one answer of each call
        return out

    monkeypatch.setattr(HistGBT, "predict", off_by_a_little)
    out, lines = run(util.make_root(tmp_path), "tiny.tiny-score")
    assert out["correct"] is False
    assert any("score_gap" in ln and "NOT OK" in ln for ln in lines)


def test_a_fit_that_returns_its_state_unchanged(tmp_path, monkeypatch):
    """``fit_device`` that boosts nothing after the warm fit: the window's
    ensemble is the warm one, a quarter of the rounds the mix asks for."""
    from dmlc_core_tpu.models import HistGBT

    real = HistGBT.fit_device
    calls = []

    def lazy(self, handle, *a, **kw):
        calls.append(1)
        if len(calls) == 1:
            self.param.n_trees = 1
            try:
                return real(self, handle, *a, **kw)
            finally:
                self.param.n_trees = 4
        return self

    monkeypatch.setattr(HistGBT, "fit_device", lazy)
    out, lines = run(util.make_root(tmp_path), "tiny.tiny-boost")
    assert out["correct"] is False
    assert any("rounds_share" in ln and "NOT OK" in ln for ln in lines)


def test_an_ingest_that_leaves_out_part_of_the_batch(tmp_path, monkeypatch):
    from dmlc_core_tpu.models import HistGBT

    real = HistGBT.make_device_data

    def half(self, X, y, *a, **kw):
        return real(self, X[: len(X) // 2], y[: len(y) // 2], *a, **kw)

    monkeypatch.setattr(HistGBT, "make_device_data", half)
    out, lines = run(util.make_root(tmp_path), "tiny.tiny-ingest")
    assert out["correct"] is False
    assert any("rows_share" in ln and "NOT OK" in ln for ln in lines)


def test_an_op_that_raises_counts_as_failed(tmp_path, monkeypatch):
    from dmlc_core_tpu.models import HistGBT

    real = HistGBT.predict
    calls = []

    def flaky(self, X, *a, **kw):
        calls.append(1)
        if len(calls) == 3:
            raise RuntimeError("boom")
        return real(self, X, *a, **kw)

    monkeypatch.setattr(HistGBT, "predict", flaky)
    out, lines = run(util.make_root(tmp_path), "tiny.tiny-score")
    assert out["failed"] == 1 and out["attempted"] > 1
    assert out["correct"] is False
