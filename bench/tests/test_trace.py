"""The trace reduction on a hand-made event list whose answer is known."""
import pytest

from bench import trace as T


def test_merge_clip_gaps():
    u = T.merge([(5, 7), (0, 2), (1, 3), (6, 9), (10, 10)])
    assert u == [(0, 3), (5, 9)]
    assert T.covered(u, 1, 6) == 2 + 1
    assert T.gaps(u, 0, 12) == [(3, 5), (9, 12)]
    assert T.clip(u, 2, 6) == [(2, 3), (5, 6)]


def test_reduce_events_by_hand():
    ms = 1_000_000
    # two calls: [0, 10] ms and [12, 22] ms; harness bookkeeping between
    spans = [("run_scanned", 0, 10 * ms), ("harness", 10 * ms, 12 * ms),
             ("run_scanned", 12 * ms, 22 * ms)]
    # device ops: busy 1-9 ms (overlapping ops) and 13-20 ms
    ops = {"/device:TPU:0": [("fusion.1", 1 * ms, 5 * ms),
                             ("while.2", 4 * ms, 9 * ms),
                             ("fusion.1", 13 * ms, 20 * ms)]}
    r = T.reduce_events(ops, spans, rounds=20)
    assert r["window_s"] == pytest.approx(0.022)
    assert r["busy_s"] == pytest.approx(0.015)
    assert r["idle_share"] == pytest.approx(1 - 15 / 22)
    # call 1: 10 ms - 8 ms busy; call 2: 10 ms - 7 ms busy
    assert r["host_ms_per_call"] == pytest.approx(2.5)
    assert r["calls"] == 2 and r["rounds"] == 20
    ops_t = dict(r["breakdown"]["device_ops"])
    assert ops_t["fusion.1"] == pytest.approx(0.011)
    assert ops_t["while.2"] == pytest.approx(0.005)
    gaps = r["breakdown"]["idle_gaps"]
    # gaps: 0-1 (call), 9-13 (midpoint 11 in harness), 20-22 (call)
    assert gaps[0] == ["harness", pytest.approx(0.004)]
    assert sorted(g[1] for g in gaps[1:]) == [pytest.approx(0.001),
                                              pytest.approx(0.002)]
    assert {g[0] for g in gaps[1:]} == {"run_scanned"}


def test_self_times_subtract_nested_ops():
    ops = [("while.1", 0, 10), ("fusion.2", 2, 4), ("fusion.3", 5, 6),
           ("fusion.2", 12, 15), ("copy.4", 14, 18)]
    t = T.self_times(ops)
    assert t == {"while.1": 7, "fusion.2": 2 + 3, "fusion.3": 1,
                 "copy.4": 4}


def test_two_devices_average_and_clip_to_window():
    spans = [("run_scanned", 100, 200)]
    ops = {"/device:TPU:0": [("a", 50, 150)],      # half outside the window
           "/device:TPU:1": [("b", 100, 200)]}
    r = T.reduce_events(ops, spans, rounds=1)
    assert r["busy_s"] == pytest.approx((50 + 100) / 2 / 1e9)
    assert r["host_ms_per_call"] == pytest.approx((100 - 75) / 1e6)


def test_no_call_span_is_an_error():
    with pytest.raises(ValueError):
        T.reduce_events({"/device:TPU:0": [("a", 0, 1)]}, [], rounds=1)
