"""The trace reduction and the roofline, on a hand-built trace."""
import re

import pytest

from bench.harness import spans, trace, work
from bench.harness.record import RunRecord
from bench.harness.spec import reader
from bench.harness.traffic import Window

MS = 1e6     # ns


def hand_trace():
    # window 0..100 ms; device 0 busy 10-20 (kernel), 30-35 (pad), 34-40
    # (kernel, overlapping the pad), 90-120 (kernel, half outside)
    ops = [("%l2_topk_pallas.1", 10 * MS, 20 * MS),
           ("%pad.3", 30 * MS, 35 * MS),
           ("%l2_topk_pallas.1", 34 * MS, 40 * MS),
           ("%l2_topk_pallas.1", 90 * MS, 120 * MS)]
    host = [("bench.flush", 5 * MS, 60 * MS),
            ("bench.launch", 8 * MS, 22 * MS),
            ("bench.launch", 28 * MS, 41 * MS)]
    return trace.Trace(devices={0: ops, 1: []}, spans=host,
                       window=(0.0, 100 * MS))


def test_busy_is_the_union_inside_the_window():
    t = hand_trace()
    assert t.used() == [0]
    assert t.busy_s() == pytest.approx((10 + 10 + 10) * 1e-3)
    assert t.window_s == pytest.approx(0.1)


def test_kernel_time_and_op_names():
    t = hand_trace()
    assert t.op_seconds(re.compile("l2_topk")) == pytest.approx(0.026)
    names = t.op_names()
    assert names["%pad.3"] == pytest.approx(0.005)


def test_gaps_are_labelled_by_the_open_span():
    t = hand_trace()
    gaps = t.gaps()
    # 0-10 (launch open at mid 5? no: launch starts at 8) -> flush, 20-30
    # (mid 25: flush only), 40-90 (mid 65: nothing open)
    assert [g[0] for g in gaps] == ["flush.engine", "flush.engine",
                                    "no-flush"]
    assert [round(g[1], 6) for g in gaps] == [0.01, 0.01, 0.05]
    b = t.breakdown()
    assert b["idle_gaps"][0] == ["no-flush", pytest.approx(0.05)]
    assert b["device_ops"][0][0] == "%l2_topk_pallas.1"


def _record(t, launches):
    win = Window(0.0, 1.0, [])
    return RunRecord(cell="x", config={}, traffic={},
                     device_kind="TPU v5 lite", setup_s=1.0, window=win,
                     spans=launches, queue_ms=[], storage_amp=1.0, trace=t)


def test_roofline_share_from_work_and_kernel_time():
    t = hand_trace()
    launch = spans.Span(spans.LAUNCH, 0, 1, rows=64, n=100_000, dim=128,
                        w=1, p=0, k=10)
    peak = work.peaks("TPU v5 lite")
    want = 100 * work.least_seconds(launch, peak) / 0.026
    got = reader("l2topk_roofline")(_record(t, [launch]))
    assert got == pytest.approx(want)
    # bytes bound: 64 queries x 128 dims is far below the ridge point
    assert work.least_seconds(launch, peak) == pytest.approx(
        work.bytes_moved(launch) / peak["hbm_bytes_per_s"])


def test_roofline_above_100_percent_is_an_error():
    t = hand_trace()
    huge = spans.Span(spans.LAUNCH, 0, 1, rows=64, n=10 ** 9, dim=768, w=8,
                      p=2, k=10)
    with pytest.raises(ValueError, match="> 100%"):
        reader("l2topk_roofline")(_record(t, [huge]))


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("TPU v99")


def test_no_trace_reads_nothing():
    rec = _record(None, [])
    assert reader("l2topk_roofline")(rec) is None
    assert reader("device_idle")(rec) is None
    assert reader("device_idle")(_record(hand_trace(), [])) == \
        pytest.approx(70.0)
