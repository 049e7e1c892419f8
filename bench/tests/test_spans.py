"""The readers of the program's spans and records (``bench/spans.py``),
on hand-built records and on a run of the harness at CPU scale."""

import time
import types

import numpy as np
import pytest

from bench import catalog, harness, spans
from bench.tests.test_check import small
from repro.serve import trace

NEW = {"dpr2m-int8.poisson": ["queue_wait_ms.poisson",
                              "turnaround_ms.poisson",
                              "dispatch_ms.poisson"],
       "dpr2m-int8-ivf.bulk": ["turnaround_ms.bulk", "dispatch_ms.bulk"]}


def _stamped(start, end=None):
    return types.SimpleNamespace(start=start, end=start if end is None
                                 else end)


def _batch(rec, bid, admitted, dispatch, fetch_end):
    """A batch dispatched at ``dispatch`` whose fetch ends at
    ``fetch_end``; its form span ends where dispatch starts."""
    rec.batch(bid, 0, 1, 1, admitted, _stamped(dispatch - 0.1, dispatch),
              _stamped(dispatch, dispatch + 0.1),
              _stamped(dispatch + 0.1, fetch_end - 0.1),
              _stamped(fetch_end - 0.1, fetch_end))


def _run(t0, t1):
    return types.SimpleNamespace(counters={"start": {"t": t0},
                                           "end": {"t": t1}})


@pytest.fixture
def recorder(monkeypatch):
    rec = trace.Recorder(capacity=16)
    monkeypatch.setattr(trace, "default_recorder", lambda: rec)
    yield rec
    rec.close()


def test_queue_wait_reads_the_window_requests_only(recorder):
    for rid, (adm, disp) in enumerate([(5.0, 5.002), (10.0, 10.003),
                                       (11.0, 11.005), (12.0, 12.004),
                                       (30.0, 30.9)]):
        recorder.request(rid, 1, adm, disp, disp + 0.01, disp + 0.02)
    w = spans.records(_run(10.0, 20.0))
    assert list(w.requests["id"]) == [1, 2, 3]
    assert spans.queue_wait_ms(w) == pytest.approx(4.0)


def test_turnaround_counts_only_batches_that_had_work_waiting(recorder):
    # fetch ends at 10.5; batch 1 had a row waiting (admitted 10.4):
    # turnaround 10.53 - 10.5.  Batch 2's row came after batch 1 ended
    # (idle, not turnaround).  Batch 3 waited again: 12.62 - 12.6.
    _batch(recorder, 0, 9.9, 10.0, 10.5)
    _batch(recorder, 1, 10.4, 10.53, 11.0)
    _batch(recorder, 2, 11.8, 12.0, 12.6)
    _batch(recorder, 3, 12.1, 12.62, 13.0)
    w = spans.records(_run(9.0, 20.0))
    assert len(w.batches) == 4
    assert spans.turnaround_ms(w) == pytest.approx(25.0)
    only_idle = spans.records(_run(11.5, 12.1))     # batch 2 alone
    assert spans.turnaround_ms(only_idle) is None


def test_dispatch_reads_dispatch_spans_in_the_window(recorder):
    t0 = time.perf_counter()
    for _ in range(3):
        with recorder.span("dispatch", batch=0):
            time.sleep(0.002)
        with recorder.span("fetch", batch=0):
            pass
    value = spans.dispatch_ms(spans.records(_run(t0, time.perf_counter())))
    assert 2.0 <= value < 50.0
    assert spans.dispatch_ms(spans.records(_run(0.0, t0))) is None


def test_none_when_the_ring_wrapped_or_the_window_is_empty(recorder):
    for rid in range(17):                       # capacity 16: one lost
        recorder.request(rid, 1, float(rid), rid + 0.5, rid + 0.6,
                         rid + 0.7)
        _batch(recorder, rid, float(rid), rid + 0.2, rid + 0.5)
    wrapped = _run(0.0, 20.0)
    assert spans.records(wrapped) is None
    for read in (spans.queue_wait_ms, spans.turnaround_ms,
                 spans.dispatch_ms):
        assert read(spans.records(wrapped)) is None
    for name in sum(NEW.values(), []):
        assert catalog.metric_reader(name)(wrapped) is None
    empty = spans.records(_run(100.0, 200.0))
    assert empty is not None
    assert spans.queue_wait_ms(empty) is None
    assert spans.turnaround_ms(empty) is None
    assert spans.dispatch_ms(empty) is None
    assert spans.records(types.SimpleNamespace(counters={})) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_cell_reads_its_new_metrics_from_a_harness_run(name):
    cell = small(name)
    assert set(NEW[name]) <= {m["name"] for m in cell.per_layer}
    t0 = time.perf_counter()
    session = harness.Session(cell, 2 ** 33 + 5, t_start=t0,
                              require_chip=False)
    try:
        run = session.window(1.0, False, t_start=t0)
    finally:
        session.close()
    for metric in NEW[name]:
        value = catalog.metric_reader(metric)(run)
        assert value is not None and np.isfinite(value) and value >= 0, \
            metric
