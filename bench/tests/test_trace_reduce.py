"""The reduction from a profiler trace to busy time, idle gaps and
operation times: on synthetic events, and on a small trace recorded on a
TPU v5e (``data/v5e_small.xplane.pb``, made by ``bench/tools/record_trace.py``)."""
import os

import pytest

from bench import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "v5e_small.xplane.pb")


def test_union_busy_and_labelled_gaps():
    ms = 1_000_000
    host = [(0, 1, "bench.window_start"), (100 * ms, 100 * ms + 1,
                                           "bench.window_end"),
            (0, 50 * ms, "bench.step"), (50 * ms, 100 * ms, "bench.wait")]
    ops = {"/device:TPU:0": [
        (-5 * ms, 10 * ms, "fusion.1"),       # clipped to the window
        (5 * ms, 20 * ms, "fusion.2"),        # overlaps: busy once
        (30 * ms, 40 * ms, "fusion.1"),
        (60 * ms, 70 * ms, "copy"),
        (120 * ms, 130 * ms, "after")]}       # outside the window
    r = trace_reduce.reduce_events(ops, host)
    assert r["window_s"] == pytest.approx(0.1, rel=1e-6)
    assert r["busy_s"] == pytest.approx(0.040)
    names = dict(r["device_ops"])
    assert names["fusion.1"] == pytest.approx(0.020)
    assert names["fusion.2"] == pytest.approx(0.015)
    assert "after" not in names
    gaps = dict(r["idle_gaps"])
    # gaps 20-30, 40-60 and 70-100 ms, each by the span at its middle
    assert gaps["bench.step"] == pytest.approx(0.010)
    assert gaps["bench.wait"] == pytest.approx(0.050)
    assert sum(gaps.values()) == pytest.approx(0.1 - 0.040)


def test_busy_is_averaged_over_chips():
    ms = 1_000_000
    host = [(0, 1, "bench.window_start"), (10 * ms, 10 * ms + 1,
                                           "bench.window_end")]
    ops = {"/device:TPU:0": [(0, 10 * ms, "a")],
           "/device:TPU:1": [(0, 5 * ms, "a")]}
    r = trace_reduce.reduce_events(ops, host)
    assert r["busy_s"] == pytest.approx(0.0075)


def test_trace_without_marks_is_refused():
    with pytest.raises(ValueError):
        trace_reduce.reduce_events({"/device:TPU:0": [(0, 1, "a")]}, [])


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_recorded_v5e_trace():
    ops, spans = trace_reduce.read_xplane(DATA, n_chips=1)
    assert len(ops) == 1 and next(iter(ops)).startswith("/device:TPU:0")
    r = trace_reduce.reduce_events(ops, spans)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["device_ops"] and r["idle_gaps"]
    assert any(name.startswith("bench.") for name, _ in r["idle_gaps"])
