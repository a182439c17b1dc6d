"""The inside view's two reductions on a small synthetic trace (known
answers), a trace without the program's marks (every new reader returns
None), and the new ``per_layer`` entries (each finds its reader by
name)."""

import json
import os

import pytest

from benchmark import harness, xplane
from benchmark.metrics import _spans

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEV = "/device:TPU:0"

NEW_METRICS = [
    "ingest.bin_device_s", "ingest.cuts_device_s", "ingest.idle_s.host_prep",
    "ingest.idle_s.cuts_put", "score.bin_device_ms",
    "score.descend_device_ms", "score.idle_ms.stack", "score.idle_ms.submit",
    "score.idle_ms.fetch", "round.hist_ms", "round.hist_ms.deepest",
    "round.nonhist_ms",
]


def test_scope_is_the_innermost_dmlc_component():
    assert _spans.scope_of(
        "jit(k_rounds_body)/while/body/closed_call/dmlc.round.L1.hist/"
        "dmlc_fused_round/pallas_call") == "dmlc.round.L1.hist"
    assert _spans.scope_of(
        "jit(k)/dmlc.round.L1.hist/dmlc.round.L1.split/reduce"
    ) == "dmlc.round.L1.split"
    assert _spans.scope_of("dmlc.bin/while") == "dmlc.bin"
    assert _spans.scope_of("jit(k)/copy") == ""
    assert _spans.scope_of("jit(not_dmlc.bin)/copy") == ""


# -- a trace file, encoded by hand ---------------------------------------------------

def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def msg(*fields):
    """A protobuf message from (number, int | bytes | str) pairs."""
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += varint(number << 3) + varint(value)
        else:
            raw = value.encode() if isinstance(value, str) else value
            out += varint(number << 3 | 2) + varint(len(raw)) + raw
    return out


def test_load_reads_scopes_from_event_metadata_and_spans_from_host(tmp_path):
    # device plane: stat 1 is tf_op, stat 2 something else; events of
    # metadata 7 (under dmlc.bin, the op_name as a string), 8 (under
    # dmlc.cuts, the op_name as a reference to a stat name) and 9 (no
    # tf_op); the line starts at 2 s; a second line is not XLA Ops
    device = msg(
        (2, "/device:TPU:0"),
        (5, msg((1, 1), (2, msg((1, 1), (2, "tf_op"))))),
        (5, msg((1, 2), (2, msg((1, 2), (2, "flops"))))),
        (5, msg((1, 3), (2, msg((1, 3), (2, "jit(f)/dmlc.cuts/sort"))))),
        (4, msg((1, 7), (2, msg((1, 7), (2, "%fusion.15 = f32[8] fusion()"),
                                (5, msg((1, 2), (4, 99))),
                                (5, msg((1, 1), (5, "jit(f)/dmlc.bin/while/"
                                                    "body/gather"))))))),
        (4, msg((1, 8), (2, msg((1, 8), (2, "%sort.6 = f32[8] sort()"),
                                (5, msg((1, 1), (7, 3))))))),
        (4, msg((1, 9), (2, msg((1, 9), (2, "%copy.1 = f32[8] copy()"))))),
        (3, msg((2, "XLA Ops"), (3, 2_000_000_000),
                (4, msg((1, 7), (2, 500_000_000_000), (3, 250_000_000_000),
                        (4, msg((1, 2), (4, 5))))),
                (4, msg((1, 8), (2, 1_000_000_000_000),
                        (3, 1_000_000_000_000))),
                (4, msg((1, 9), (3, 1_000_000))))),
        (3, msg((2, "XLA Modules"), (4, msg((1, 7), (3, 5))))))
    host = msg(
        (2, "/host:CPU"),
        (5, msg((1, 1), (2, msg((1, 1), (2, "op"))))),
        (4, msg((1, 1), (2, msg((1, 1), (2, "dmlc.ingest.pad"))))),
        (4, msg((1, 2), (2, msg((1, 2), (2, "bench.op"))))),
        (3, msg((2, "python3"), (3, 1_000_000_000),
                (4, msg((1, 1), (2, 250_000_000_000), (3, 500_000_000_000),
                        (4, msg((1, 1), (4, 42))))),
                (4, msg((1, 2), (2, 0), (3, 9_000_000_000_000))))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(msg((1, host), (1, device)))
    m = _spans.load(str(path))
    assert m.device_ops == [[("dmlc.bin", pytest.approx(2.5),
                              pytest.approx(2.75)),
                             ("dmlc.cuts", pytest.approx(3.0),
                              pytest.approx(4.0)),
                             ("", pytest.approx(2.0),
                              pytest.approx(2.000001))]]
    assert m.spans == [("dmlc.ingest.pad", pytest.approx(1.25),
                        pytest.approx(1.75), 42)]


def ctx_of(device_ops, spans, host_events, ops=2, work=50.0):
    """A run's context over a synthetic trace: window 0..10 s."""
    planes = {
        DEV: {xplane.OPS_LINE: [(s or "op", a, b) for s, a, b in device_ops],
              xplane.MODULES_LINE: []},
        "/host:CPU": {"main": [("bench.window", 0.0, 10.0)] + host_events},
    }
    ctx = harness.Ctx(root=ROOT, workload="w", config={}, mix={}, seed=0,
                      chips=1)
    ctx.summary = xplane.summarize(planes)
    ctx.op_seconds = [1.0] * ops
    ctx.op_work = [work] * ops
    ctx.state["_spans.marks"] = _spans.Marks(spans, [device_ops])
    return ctx


# a round program: a scan ('while', no scope) 1..9 whose body holds the
# histogram kernels of two levels, a split fused under its own scope, and
# an unscoped copy; after it 0.5 s of binning that runs past the window
DEVICE_OPS = [
    ("", 1.0, 9.0),                              # the while
    ("dmlc.round.L0.hist", 1.0, 2.0),
    ("dmlc.round.L0.split", 2.0, 2.5),
    ("dmlc.round.L1.hist", 3.0, 6.0),
    ("dmlc.round.L1.split", 6.0, 6.5),
    ("", 7.0, 7.5),
    ("dmlc.bin", 9.5, 10.5),
]


def test_self_time_by_scope_of_a_nested_while():
    by = _spans.self_seconds_by_scope([DEVICE_OPS], (0.0, 10.0))
    assert by["dmlc.round.L0.hist"] == pytest.approx(1.0)
    assert by["dmlc.round.L1.hist"] == pytest.approx(3.0)
    assert by["dmlc.round.L0.split"] == pytest.approx(0.5)
    assert by["dmlc.bin"] == pytest.approx(0.5)          # clipped at 10
    # the while less its scoped children, plus the unscoped copy inside:
    # 8 - (1 + .5 + 3 + .5 + .5) + .5
    assert by[""] == pytest.approx(3.0)
    assert sum(by.values()) == pytest.approx(8.5)        # = busy: no
    # second is counted twice
    two = _spans.self_seconds_by_scope([DEVICE_OPS, []], (0.0, 10.0))
    assert two["dmlc.bin"] == pytest.approx(0.25)        # device average


def test_idle_inside_spans_by_operation():
    busy = xplane.merge((a, b) for _s, a, b in DEVICE_OPS)
    spans = [
        ("dmlc.predict.put", 0.2, 0.6, 1),       # all idle: 0.4
        ("dmlc.predict.dispatch", 0.6, 1.5, 1),  # idle until 1.0: 0.4
        ("dmlc.predict.fetch", 1.5, 9.2, 1),     # busy to 9.0: 0.2
        ("dmlc.predict.put", 9.2, 9.4, 2),       # 0.2
        ("dmlc.predict.dispatch", 9.4, 9.9, 2),  # busy from 9.5: 0.1
        ("dmlc.predict.fetch", 9.9, 11.0, 2),    # straddles the window's
        # end: counts to 10.0, all of it busy
        ("dmlc.predict.fetch", -2.0, -1.0, 0),   # outside: dropped
    ]
    w = (0.0, 10.0)
    assert _spans.idle_by_op(spans, ["dmlc.predict.put",
                                     "dmlc.predict.dispatch"], busy, w
                             ) == [pytest.approx(0.8), pytest.approx(0.3)]
    assert _spans.idle_by_op(spans, ["dmlc.predict.fetch"], busy, w
                             ) == [pytest.approx(0.2), pytest.approx(0.0)]
    assert _spans.idle_by_op(spans, ["dmlc.predict.stack"], busy, w) == []
    # spans without an op are operations of their own
    assert len(_spans.idle_by_op([("a", 0.0, 0.5, None), ("a", 0.5, 1.0,
                                                          None)],
                                 ["a"], busy, w)) == 2


def read(ctx, metric):
    path = harness.find_file(ROOT, ["benchmark"], "metrics", metric + ".py")
    return harness.load_module(path).read(ctx)


def test_readers_on_the_synthetic_round_program():
    ctx = ctx_of(DEVICE_OPS, [], [], ops=2, work=50.0)     # 100 rounds
    assert read(ctx, "round.hist_ms") == pytest.approx(40.0)
    assert read(ctx, "round.hist_ms.deepest") == pytest.approx(30.0)
    assert read(ctx, "round.nonhist_ms") == pytest.approx(45.0)
    assert read(ctx, "score.bin_device_ms") == pytest.approx(250.0)
    assert read(ctx, "ingest.bin_device_s") == pytest.approx(0.25)
    assert read(ctx, "ingest.cuts_device_s") is None


def test_idle_readers_take_the_median_over_operations():
    spans = [("dmlc.predict.stack", 0.0 + i, 0.1 * (i + 1) + i, i)
             for i in range(3)]                  # idle .1, busy, busy
    ctx = ctx_of([("dmlc.descend", 1.0, 9.0)], spans, [], ops=3)
    assert read(ctx, "score.idle_ms.stack") == pytest.approx(0.0)
    ctx = ctx_of([], [("dmlc.ingest.host_prep", 1.0, 3.5, 7),
                      ("dmlc.ingest.cuts", 3.5, 5.0, 7)], [], ops=1)
    assert read(ctx, "ingest.idle_s.host_prep") == pytest.approx(2.5)
    assert read(ctx, "ingest.idle_s.cuts_put") == pytest.approx(1.5)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_no_marks_in_the_trace_reads_none(metric):
    # the parent's trace: device operations under no scope, the harness's
    # own spans, nothing named dmlc.*
    ctx = ctx_of([("", 1.0, 5.0), ("", 6.0, 8.0)], [],
                 [("bench.op", 0.5, 9.0)])
    assert read(ctx, metric) is None


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_entries_find_their_readers_by_name(metric):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == metric]
    assert entry["source"] == "device_trace" and entry["better"] == "lower"
    cells = {w["name"] for w in bench["workloads"]}
    moved = {m["name"]: m for m in bench["end_to_end"]}[entry["moves"]]
    assert set(entry["workloads"]) <= cells & set(moved["workloads"])
    path = harness.find_file(ROOT, bench["paths"], "metrics",
                             metric + ".py")
    assert callable(harness.load_module(path).read)
