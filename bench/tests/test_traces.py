"""The trace reduction: busy union, idle share and breakdown names."""

from pathlib import Path

import numpy as np
import pytest

from bench import traces

SAMPLE = Path(__file__).resolve().parent / "data" / "sample.xplane.pb"


def test_busy_is_the_union_of_overlapping_operations():
    ops = {"/device:TPU:0": [("a", 0.0, 10e6), ("b", 5e6, 10e6),
                             ("a", 30e6, 5e6)]}
    spans = [("bench.submit", 14e6, 20e6)]
    r = traces.reduce_events(ops, spans, window_s=0.05)
    assert r.busy_s == pytest.approx(0.020)          # [0, 15] ∪ [30, 35] ms
    assert r.device_ops == [("a", pytest.approx(0.015)),
                            ("b", pytest.approx(0.010))]
    assert r.idle_gaps == [("bench.submit", pytest.approx(0.015))]
    assert r.breakdown()["idle_gaps"] == [["bench.submit",
                                           pytest.approx(0.015)]]


def test_busy_is_averaged_over_devices_and_gaps_without_spans_are_named():
    ops = {"/device:TPU:0": [("x", 0.0, 4e6)],
           "/device:TPU:1": [("x", 0.0, 2e6), ("x", 6e6, 2e6)]}
    r = traces.reduce_events(ops, [], window_s=0.01)
    assert r.busy_s == pytest.approx(0.004)
    assert r.n_devices == 2
    assert r.idle_gaps == [(traces.NO_SPAN, pytest.approx(0.004))]


def test_merge_intervals():
    m = traces.merge_intervals(np.array([[3.0, 4.0], [0.0, 2.0],
                                         [1.0, 2.5]]))
    np.testing.assert_array_equal(m, [[0.0, 2.5], [3.0, 4.0]])


def test_the_recorded_chip_trace():
    """``data/sample.xplane.pb``, recorded on one v5e by
    ``data/record_trace.py``: twelve searches, each after a 20 ms
    ``bench.submit`` span of host sleep."""
    device_ops, spans = traces.read_xplane(SAMPLE)
    assert list(device_ops) == ["/device:TPU:0"]
    assert sum(n == "bench.submit" for n, _, _ in spans) == 12
    assert sum(n == "bench.collect" for n, _, _ in spans) == 12
    r = traces.reduce_events(device_ops, spans, window_s=0.3041)
    # device busy is the union of the ops, well under the window
    assert 0.03 < r.busy_s < 0.06
    total = sum(d for _, _, d in device_ops["/device:TPU:0"]) * 1e-9
    assert r.busy_s <= total + 1e-9
    # the eleven gaps between searches are the host's 20 ms sleeps
    longest = r.idle_gaps[:11]
    assert all(name == "bench.submit" for name, _ in longest)
    assert all(0.02 < s < 0.03 for _, s in longest)
    # the scan is the custom call of the jitted search
    assert r.device_ops[0][0] == "jit_search/%custom-call"
    b = r.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) == 10
