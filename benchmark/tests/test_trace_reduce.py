"""The trace reduction, on a hand-made trace and on a small recorded one."""

import json
import os

import pytest

from benchmark import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "trace_events.json")
MS = 1_000_000


def test_busy_idle_ops_and_gaps_by_hand():
    window = (0, 100 * MS)
    devices = {"/device:TPU:0": [
        ("fusion.1", 10 * MS, 10 * MS),      # 10-20
        ("fusion.2", 15 * MS, 10 * MS),      # 15-25, overlaps
        ("while.3", 60 * MS, 30 * MS),       # 60-90
        ("fusion.1", 95 * MS, 10 * MS),      # 95-105, clipped to 100
        ("early", -20 * MS, 5 * MS),         # outside the window
    ]}
    host = [
        ("benchmark.window", 0, 100 * MS),
        ("benchmark.catchup_rpc", 0, 100 * MS),
        ("PJRT_LoadedExecutable_Execute", 30 * MS, 20 * MS),  # 30-50
    ]
    out = trace_reduce.reduce_events(devices, host, window)
    assert out["window_s"] == pytest.approx(0.1)
    # busy: 10-25, 60-90, 95-100 = 15 + 30 + 5 ms
    assert out["busy_s"] == pytest.approx(0.050)
    assert out["device_ops"][0] == ["while.3", pytest.approx(0.030)]
    assert dict(out["device_ops"])["fusion.1"] == pytest.approx(0.015)
    # gaps: 0-10 (rpc), 25-60 (its middle 42.5 ms is inside the Execute
    # call, which started last), 90-95 (rpc); longest first
    assert out["idle_gaps"] == [
        ["PJRT_LoadedExecutable_Execute", pytest.approx(0.035)],
        ["benchmark.catchup_rpc", pytest.approx(0.010)],
        ["benchmark.catchup_rpc", pytest.approx(0.005)],
    ]


def test_two_chips_average_and_unattributed_gaps():
    window = (0, 10 * MS)
    devices = {"/device:TPU:0": [("a", 0, 10 * MS)],
               "/device:TPU:1": [("b", 0, 2 * MS)]}
    out = trace_reduce.reduce_events(devices, [], window)
    assert out["busy_s"] == pytest.approx(0.006)
    assert out["idle_gaps"] == [["unattributed", pytest.approx(0.008)]]


def test_empty_window_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce_events({}, [], (5, 5))


def test_recorded_chip_trace():
    """A 150 ms slice of a traced ``string-10k.bulk`` window on one v5e
    (``--dump-trace``, op names shortened as ``events_from_xplane`` does),
    around the chip's first op; the numbers beside it were computed by a
    plain per-microsecond scan of the same events."""
    with open(RECORDED) as f:
        rec = json.load(f)
    out = trace_reduce.reduce_events(
        {k: [tuple(e) for e in v] for k, v in rec["devices"].items()},
        [tuple(e) for e in rec["host"]], tuple(rec["window"]))
    want = rec["expected"]
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-3)
    assert out["window_s"] == pytest.approx(want["window_s"])
    assert [n for n, _ in out["device_ops"]] == want["top_ops"]
    assert 0 < out["busy_s"] < out["window_s"]
