"""The program's spans read beside the benchmark's: per-flush sums, the
mapping onto the trace clock, and the idle time under the kernel
wrapper's host preparation, on a hand-built trace with a known offset."""
import time

import pytest

from bench.harness import progspans, spans, trace
from bench.harness.record import RunRecord
from bench.harness.spec import reader
from bench.harness.traffic import Window
from repro import obs

MS = 1e6                   # ns
OFF = 7_777_000_123.0      # trace ns - perf_counter ns
NEW = ("prep_ms", "readback_ms", "h2d_mb", "plan_ms", "merge_ms",
       "idle_prep_pct")


def prog(name, t0_ms, t1_ms, sid, parent=None, root=None, **attrs):
    s = obs.Span(name, attrs)
    s.t0, s.t1 = t0_ms * 1e-3, t1_ms * 1e-3
    s.id, s.parent, s.root = sid, parent, root or sid
    return s


def program():
    # two flushes: 10-50 ms and 60-90 ms (perf clock, ms); each root closes
    # after the spans of its tree
    return [
        prog("search.plan", 10.5, 12.5, 2, 1, 1),
        prog("l2_topk.prep", 13, 30, 4, 3, 1, h2d_bytes=3_000_000),
        prog("l2_topk.dispatch", 30, 31, 5, 3, 1),
        prog("scan.readback", 31, 40, 6, 3, 1),
        prog("scan.launch", 13, 40, 3, 1, 1),
        prog("search.merge", 40, 41, 7, 1, 1),
        prog("serve.flush", 9.5, 50.5, 1, flush=0),
        prog("search.plan", 60.5, 61.5, 9, 8, 8),
        prog("l2_topk.prep", 62, 70, 10, 8, 8, h2d_bytes=1_000_000),
        prog("scan.readback", 71, 75, 11, 8, 8),
        prog("search.merge", 75, 78, 12, 8, 8),
        prog("serve.flush", 59.5, 90.5, 8, flush=1),
    ]


def recorded():
    rec = obs.Recorder()
    rec.counters["h2d_bytes"] = 0          # a counter the program counts
    for s in program():
        rec.add(s)
    return rec


def ns(ms):
    return ms * MS + OFF


def hand_run(shift_ms=0.0):
    # the device is busy 0-20 and 35-100 ms (perf clock): idle 20-35
    # (under the first prep until 30, then dispatch and read-back) and
    # 100-120 (no flush)
    flushes = [spans.Span(spans.FLUSH, 0.010, 0.050, rows=64),
               spans.Span(spans.FLUSH, 0.060, 0.090, rows=64)]
    events = [(spans.FLUSH, ns(f.t0 * 1e3 + shift_ms),
               ns(f.t1 * 1e3 + shift_ms)) for f in flushes]
    ops = [("%l2_topk_pallas.1", ns(0), ns(20)),
           ("%pad.1", ns(35), ns(100))]
    t = trace.Trace(devices={0: ops}, spans=events,
                    window=(ns(0), ns(120)))
    return RunRecord(cell="x", config={}, traffic={},
                     device_kind="TPU v5 lite", setup_s=1.0,
                     window=Window(0.0, 0.12, []), spans=flushes,
                     queue_ms=[], storage_amp=1.0, trace=t)


@pytest.fixture
def with_program(monkeypatch):
    rec = recorded()
    monkeypatch.setattr(progspans, "recorder", lambda: rec)


def test_mapping_recovers_the_offset(with_program):
    cmap = progspans.mapped(hand_run())
    assert cmap.offset_ns == pytest.approx(OFF, abs=1e-3)
    assert cmap.start_err_ns == pytest.approx(0.0, abs=1e-3)
    prep = [(a, b) for s, a, b in cmap.spans if s.name == "l2_topk.prep"]
    assert prep[0] == (pytest.approx(ns(13)), pytest.approx(ns(30)))


def test_a_mapping_off_by_more_than_1_ms_is_refused(with_program):
    # the second flush's trace event ends 3 ms after the program's
    # serve.flush that should hold it
    run = hand_run()
    name, a, b = run.trace.spans[1]
    run.trace.spans[1] = (name, a, b + 3 * MS)
    assert progspans.mapped(run) is None
    assert reader("idle_prep_pct")(run) is None
    # within 1 ms it stands, whatever the offset
    run.trace.spans[1] = (name, a, b + 0.9 * MS)
    assert progspans.mapped(run) is not None
    cmap = progspans.mapped(hand_run(shift_ms=250.0))
    assert cmap.offset_ns == pytest.approx(OFF + 250 * MS, abs=1e-3)


def test_idle_prep_counts_only_idle_time_under_prep(with_program):
    run = hand_run()
    # idle 20-35 and 100-120; prep open 13-30 and 62-70: overlap 20-30
    assert progspans.idle_under(run, "l2_topk.prep") == \
        (pytest.approx(0.010), pytest.approx(0.120))
    assert reader("idle_prep_pct")(run) == pytest.approx(100 * 10 / 120)
    assert progspans.idle_under(run, "scan.readback")[0] == \
        pytest.approx(0.004)


def test_trees_let_go_leave_the_sums_and_the_rest_of_the_window(
        monkeypatch):
    # a recorder that holds 8 spans in trees: the first flush's tree (7)
    # goes when the second (5) closes
    monkeypatch.setattr(obs, "RING", 8)
    rec = recorded()
    monkeypatch.setattr(progspans, "recorder", lambda: rec)
    run = hand_run()
    assert rec.held_from() == pytest.approx(0.0505)
    assert reader("prep_ms")(run) == pytest.approx((17 + 8) / 2)
    cmap = progspans.mapped(run)
    assert cmap.from_ns == pytest.approx(ns(50.5))
    assert {s.root for s, _, _ in cmap.spans} == {8}
    # from 50.5 ms on: idle 100-120 only, when no prep span is open
    assert progspans.idle_under(run, "l2_topk.prep") == \
        (pytest.approx(0.0), pytest.approx(0.0695))
    assert reader("idle_prep_pct")(run) == pytest.approx(0.0)


def test_per_flush_readers(with_program):
    run = hand_run()
    assert reader("prep_ms")(run) == pytest.approx((17 + 8) / 2)
    assert reader("readback_ms")(run) == pytest.approx((9 + 4) / 2)
    assert reader("h2d_mb")(run) == pytest.approx(2.0)
    assert reader("plan_ms")(run) == pytest.approx(1.5)
    assert reader("merge_ms")(run) == pytest.approx(2.0)


def test_no_program_spans_reads_nothing():
    # a window that starts after every span the recorder holds
    run = hand_run()
    later = time.perf_counter() + 3600.0
    run.window = Window(later, later + 0.12, [])
    assert progspans.flush_roots(run) is None
    for name in NEW:
        assert reader(name)(run) is None, name


def test_an_older_program_reads_nothing(monkeypatch):
    import builtins
    real = builtins.__import__

    def no_obs(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "repro" and fromlist and "obs" in fromlist:
            raise ImportError("cannot import name 'obs'")
        return real(name, globals, locals, fromlist, level)
    monkeypatch.setattr(builtins, "__import__", no_obs)
    run = hand_run()
    for name in NEW:
        assert reader(name)(run) is None, name
