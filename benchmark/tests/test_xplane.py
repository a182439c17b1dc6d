"""The trace reduction on a small synthetic event list: busy, idle, gaps,
self times and span arithmetic have known answers here."""

import pytest

from benchmark import xplane

DEV = "/device:TPU:0"


def planes():
    # seconds.  Window 0..10.  A 'while' 1..5 holding two kernels; a
    # lone sort 6..8; the programs that ran them; two harness op spans.
    return {
        DEV: {
            xplane.OPS_LINE: [
                ("while.1", 1.0, 5.0),
                ("hist_kernel", 1.0, 2.5),
                ("fusion.7", 3.0, 4.0),
                ("sort.3", 6.0, 8.0),
            ],
            xplane.MODULES_LINE: [
                ("jit_rounds_body(1)", 1.0, 5.0),
                ("jit_sort(2)", 6.0, 8.0),
            ],
        },
        "/host:CPU": {
            "main": [("bench.window", 0.0, 10.0), ("bench.op", 0.5, 5.5),
                     ("bench.op", 5.5, 9.0), ("not_ours", 0.0, 10.0)],
        },
    }


def test_busy_idle_and_window():
    s = xplane.summarize(planes())
    assert s.window == (0.0, 10.0)
    assert s.window_s == pytest.approx(10.0)
    assert s.busy_s == pytest.approx(6.0)          # 1..5 and 6..8
    assert s.devices[0].busy == [(1.0, 5.0), (6.0, 8.0)]


def test_self_times_do_not_count_a_second_twice():
    s = xplane.summarize(planes())
    ops = s.devices[0].op_self_s
    assert ops["hist_kernel"] == pytest.approx(1.5)
    assert ops["fusion.7"] == pytest.approx(1.0)
    assert ops["while.1"] == pytest.approx(1.5)    # 4 s less its children
    assert ops["sort.3"] == pytest.approx(2.0)
    assert sum(ops.values()) == pytest.approx(s.busy_s)
    assert s.op_seconds(lambda n: "sort" in n) == pytest.approx(2.0)
    assert s.top_ops(1)[0][0] == "sort.3"


def test_gaps_are_named_by_span_and_neighbours():
    s = xplane.summarize(planes())
    gaps = dict(s.idle_gaps())
    assert gaps["bench.op:start->jit_rounds_body(1)"] == pytest.approx(1.0)
    assert gaps["bench.op:jit_rounds_body(1)->jit_sort(2)"] == pytest.approx(1.0)
    assert gaps["bench.window:jit_sort(2)->end"] == pytest.approx(2.0)
    assert s.top_gaps(1)[0][1] == pytest.approx(2.0)
    assert s.module_gaps(lambda n: n.startswith("jit_")) == [pytest.approx(1.0)]


def test_span_arithmetic():
    s = xplane.summarize(planes())
    # op 1: 5 s long, 4 s busy; op 2: 3.5 s long, 2 s busy
    assert s.idle_in_spans("bench.op") == [pytest.approx(1.0),
                                           pytest.approx(1.5)]
    assert s.busy_share_in_spans("bench.op") == pytest.approx(100 * 6 / 8.5)
    assert s.busy_share_in_spans("bench.nothing") is None


def test_window_clips_events_and_devices_average():
    p = planes()
    p["/device:TPU:1"] = {xplane.OPS_LINE: [("sort.3", 9.0, 12.0)],
                          xplane.MODULES_LINE: []}
    s = xplane.summarize(p)
    assert [d.name for d in s.devices] == [DEV, "/device:TPU:1"]
    assert s.devices[1].busy_s == pytest.approx(1.0)   # clipped at 10
    assert s.busy_s == pytest.approx(3.5)


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        xplane.summarize({"/host:CPU": {"main": [("bench.window", 0, 1)]}})


def test_interval_helpers():
    assert xplane.merge([(3, 4), (1, 2), (1.5, 3.5), (9, 9)]) == [(1, 4)]
    assert xplane.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert xplane.busy_within([(1, 2), (3, 4)], 1.5, 3.5) == pytest.approx(1.0)


def test_short_name_of_hlo_text():
    hlo = ('%closed_call.45 = (f32[32,64,128]{2,1,0:T(8,128)S(1)}, '
           's32[1,16007168]{1,0:T(1,128)}) custom-call(u8[32,16007168]'
           '{1,0:T(8,128)(4,1)} %pad.488), '
           'custom_call_target="tpu_custom_call", operand_layout')
    assert xplane.short_name(hlo) == (
        "closed_call.45 custom-call/tpu_custom_call "
        "(f32[32,64,128], s32[1,16007168])")
    assert xplane.short_name(
        "%sort.6 = (f32[16,28]{0,1:T(8,128)}, s32[16,28]{0,1}) "
        "sort(f32[16,28]{0,1} %x, s32[16,28]{0,1} %iota.3), dimensions={0}"
    ) == "sort.6 sort (f32[16,28], s32[16,28])"
    assert xplane.short_name("jit_f(123)") == "jit_f(123)"


def test_readers_find_kernels_by_opcode():
    from benchmark.metrics import _names

    assert _names.is_hist_kernel(
        "closed_call.45 custom-call/tpu_custom_call (f32[32,64,128])")
    assert not _names.is_hist_kernel("fusion.16 fusion f32[56000000]")
    assert _names.is_sort("sort.6 sort (f32[16,28], s32[16,28])")
    assert _names.is_all_reduce("all-reduce.3 all-reduce f32[2,16,28,256]")
    assert _names.is_all_reduce("ar.1 all-reduce-start f32[2]")
    assert _names.is_round_program("jit_k_rounds_body")
