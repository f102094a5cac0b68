"""The reduction from a profiler trace to a window's device numbers."""
import json
from pathlib import Path

import pytest

from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data"


def ev(name, s, t):
    return tr.Event(name, float(s), float(t))


def test_merged_clips_and_unions():
    events = [ev("a", 0, 10), ev("b", 5, 20), ev("c", 30, 40),
              ev("d", 45, 60)]
    assert tr.merged(events, 2, 50) == [[2, 20], [30, 40], [45, 50]]
    assert tr.merged(events, 60, 70) == []


def test_window_reduction_counts_busy_ops_and_named_gaps():
    device = {"/device:TPU:0": [ev("fusion.1", 10, 40), ev("scatter", 40, 60),
                                ev("fusion.1", 80, 90)]}
    host = [ev("service.dispatch", 0, 100), ev("engine.collect_results",
                                               60, 75),
            ev("unrelated", 0, 100)]
    r = tr.reduce_window(device, host, 0, 100, ["/device:TPU:0"])
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(60e-9)
    assert r.device_ops == [["fusion.1", pytest.approx(40e-9)],
                            ["scatter", pytest.approx(20e-9)]]
    # gaps 0-10, 75-80 and 90-100 lie under the dispatch span only;
    # 60-75 under the innermost span, the result collection
    assert dict(r.idle_gaps) == {
        "service.dispatch": pytest.approx(25e-9),
        "engine.collect_results": pytest.approx(15e-9)}


def test_busy_time_is_averaged_over_the_chips_used():
    device = {"/device:TPU:0": [ev("x", 0, 50)],
              "/device:TPU:1": [ev("x", 0, 30)],
              "/device:TPU:2": [ev("x", 0, 100)]}
    r = tr.reduce_window(device, [], 0, 100,
                         ["/device:TPU:0", "/device:TPU:1"])
    assert r.busy_s == pytest.approx(40e-9)
    assert dict(r.idle_gaps) == {tr.NO_SPAN: pytest.approx(60e-9)}


def test_no_device_plane_reads_nothing():
    assert tr.reduce_window({}, [], 0, 100, ["/device:TPU:0"]) is None


def test_self_times_take_nested_ops_out_of_their_parent():
    ops = {}
    tr.self_times([ev("while", 0, 100), ev("body", 10, 40),
                   ev("body", 50, 90), ev("after", 100, 120)], 0, 110, ops)
    assert ops == {"while": 30.0, "body": 70.0, "after": 10.0}


@pytest.fixture(scope="module")
def recorded():
    """A trace recorded on one TPU v5e chip: three calls of a jitted
    while loop, each in a ``service.dispatch`` span and followed by a
    20 ms ``engine.collect_results`` span."""
    meta = json.loads((DATA / "v5e_small.json").read_text())
    device, host = tr.load(DATA / "v5e_small.xplane.pb")
    return meta, device, host, tr.clock_offset_ns(host, meta["t_mark"])


def test_recorded_trace_has_device_ops_on_the_host_clock(recorded):
    meta, device, host, off = recorded
    assert meta["device_kind"] == "TPU v5 lite"
    assert list(device) == ["/device:TPU:0"]
    ops = device["/device:TPU:0"]
    assert ops and all(" " not in e.name for e in ops)
    dispatch = [e for e in host if e.name == "service.dispatch"]
    assert len(dispatch) == 3
    # device and host clocks agree to a few milliseconds
    slack = 5e6
    for e in ops:
        assert any(d.start_ns - slack <= e.start_ns <= d.end_ns + slack
                   for d in dispatch)
    lo, hi = meta["t_open"] * 1e9 + off, meta["t_close"] * 1e9 + off
    assert all(lo - slack <= d.start_ns and d.end_ns <= hi + slack
               for d in dispatch)


def test_recorded_trace_reduces_to_busy_time_and_named_gaps(recorded):
    meta, device, host, off = recorded
    lo, hi = meta["t_open"] * 1e9 + off, meta["t_close"] * 1e9 + off
    r = tr.reduce_window(device, host, lo, hi, ["/device:TPU:0"])
    assert r.window_s == pytest.approx(meta["t_close"] - meta["t_open"])
    assert 0 < r.busy_s < 0.01 * r.window_s
    names = [n for n, _ in r.device_ops]
    assert "while" in names and len(names) <= 10
    gaps = dict(r.idle_gaps)
    # three 20 ms sleeps under the result-collection span
    assert gaps["engine.collect_results"] == pytest.approx(0.062, abs=0.006)
    assert sum(gaps.values()) + r.busy_s == pytest.approx(r.window_s)
