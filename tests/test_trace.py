"""The serving path's recorder: rings, spans, the gc hook, and the spans
and records a RetrievalService writes along its drain path."""

import gc
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.retrieval import IndexSpec, build_index
from repro.serve import MicroBatcher, RetrievalService
from repro.serve import trace
from repro.serve.trace import FLUSH_ROWS, SPAN_NAMES, Recorder

D = 32
DRAIN_PATH = ["admit", "drain", "form", "dispatch", "device_wait", "flush",
              "fetch", "scatter", "resolve"]


@pytest.fixture(scope="module")
def index():
    rng = np.random.default_rng(3)
    docs = rng.standard_normal((400, D)).astype(np.float32)
    fit = rng.standard_normal((32, D)).astype(np.float32)
    return build_index(IndexSpec(method="int8", backend="jnp", post=False),
                       jnp.asarray(docs), jnp.asarray(fit))


@pytest.fixture
def rec(monkeypatch):
    """A fresh recorder as the process-wide one, for services and engines
    made in the test."""
    r = Recorder()
    monkeypatch.setattr(trace, "_default", r)
    yield r
    r.close()


def _queries(n, seed=0):
    return np.random.default_rng(seed).standard_normal((n, D)).astype(
        np.float32)


def _names(spans):
    return [SPAN_NAMES[i] for i in spans["name"]]


def test_ring_holds_capacity_and_window_is_none_past_a_wrap():
    r = Recorder(capacity=4)
    try:
        for i in range(4):
            r.request(i, 1, float(i), i + 0.1, i + 0.2, i + 0.3)
        w = r.window(0.0, 10.0)
        assert w is not None and list(w.requests["id"]) == [0, 1, 2, 3]
        r.request(4, 1, 4.0, 4.1, 4.2, 4.3)      # overwrites admitted 0.0
        assert r.window(0.0, 10.0) is None
        assert r.window(-5.0, 10.0) is None
        w = r.window(0.5, 10.0)
        assert list(w.requests["id"]) == [1, 2, 3, 4]
        assert list(r.window(1.5, 3.5).requests["id"]) == [2, 3]
    finally:
        r.close()


def test_one_flush_past_capacity_keeps_the_newest_rows():
    r = Recorder(capacity=4)
    try:
        for i in range(6):                       # pending until the read
            r.request(i, 1, float(i), i + 0.1, i + 0.2, i + 0.3)
        assert r.window(1.0, 10.0) is None       # admitted 1.0 was lost
        w = r.window(1.5, 10.0)
        assert list(w.requests["id"]) == [2, 3, 4, 5]
    finally:
        r.close()


def test_span_ring_wrap_and_window_filter():
    r = Recorder(capacity=8)
    gc.disable()                 # no repro.gc span may take a ring row
    try:
        for _ in range(8):
            with r.span("admit"):
                pass
        full = r.window(-np.inf, np.inf)
        assert full is not None
        first, last = full.spans["start"][0], full.spans["start"][-1]
        assert len(r.window(first, last).named("admit")) == 7   # [t0, t1)
        with r.span("admit"):
            pass
        assert r.window(first, np.inf) is None
        assert r.window(np.nextafter(first, np.inf), np.inf) is not None
    finally:
        gc.enable()
        r.close()


def test_span_parent_ids_and_nesting(rec):
    with rec.span("drain") as outer:
        with rec.span("form", batch=7) as inner:
            pass
        other = {}

        def elsewhere():
            with rec.span("admit") as s:
                other["span"] = s
        t = threading.Thread(target=elsewhere)
        t.start()
        t.join()
    assert outer.parent == -1 and inner.parent == outer.id
    assert other["span"].parent == -1           # stacks are per thread
    assert outer.start <= inner.start <= inner.end <= outer.end
    w = rec.window(-np.inf, np.inf)
    row = w.named("form")[0]
    assert (row["id"], row["parent"], row["batch"]) == (inner.id, outer.id, 7)
    assert (row["start"], row["end"]) == (inner.start, inner.end)


def test_gc_hook_counts_a_forced_collection(rec):
    (n0, p0) = rec.gc_counts()
    t0 = time.perf_counter()
    gc.collect(2)
    n1, p1 = rec.gc_counts()
    assert n1[2] >= n0[2] + 1 and p1[2] > p0[2]
    spans = rec.window(t0, np.inf).named("gc")
    assert len(spans) >= 1 and np.all(spans["end"] >= spans["start"])


def test_service_emits_the_drain_path_in_order(index, rec):
    with RetrievalService(start=False) as svc:
        svc.register("kb", index)
        t0 = time.perf_counter()
        h = svc.query(_queries(3), index="kb", k=5)
        assert svc.drain_once() == 1
        h.result(timeout=30)
        w = rec.window(t0, np.inf)
        engine = svc.engine("kb")
    spans = w.spans[w.spans["name"] != SPAN_NAMES.index("gc")]
    assert _names(spans) == DRAIN_PATH
    by = {n: s for n, s in zip(_names(spans), spans)}
    assert len(w.batches) == 1
    bid = w.batches["id"][0]
    for name in ("form", "dispatch", "device_wait", "fetch", "scatter"):
        assert by[name]["batch"] == bid
        assert by[name]["parent"] == by["drain"]["id"]
    # the flush lies inside device_wait, so inside the search interval
    assert by["flush"]["batch"] == bid
    assert by["flush"]["parent"] == by["device_wait"]["id"]
    assert by["resolve"]["parent"] == by["drain"]["id"]
    assert w.batches["drain"][0] == by["drain"]["id"]
    b = w.batches[0]
    assert (b["n_valid"], b["rows"]) == (3, 4)
    assert b["dispatch_start"] == by["dispatch"]["start"]
    assert b["fetch_end"] == by["fetch"]["end"]
    assert engine.stats()["rows_padded"] == 1


def test_request_records_are_ordered_and_match_the_results(index, rec):
    with RetrievalService(start=False,
                          batcher=MicroBatcher(max_batch=4)) as svc:
        svc.register("kb", index)
        t0 = time.perf_counter()
        handles = [svc.query(_queries(n, seed=n), index="kb", k=5)
                   for n in (1, 6, 2)]
        svc.drain_once()
        results = {h.request_id: h.result(timeout=30) for h in handles}
        w = rec.window(t0, np.inf)
    r = w.requests
    assert sorted(r["id"]) == sorted(results)
    assert np.all(r["admitted"] <= r["dispatched"])
    assert np.all(r["dispatched"] <= r["done"])
    assert np.all(r["done"] <= r["resolved"])
    for row in r:
        res = results[int(row["id"])]
        assert (row["admitted"], row["dispatched"], row["done"]) == (
            res.admitted_s, res.dispatched_s, res.done_s)
        assert res.latency_s == res.done_s - res.admitted_s
        assert row["rows"] == res.ids.shape[0]
    # the 6-row request spans two batches: dispatched at the first's start
    six = r[r["rows"] == 6][0]
    firsts = w.batches["dispatch_start"]
    assert six["dispatched"] == firsts.min() < six["done"]
    assert len(w.batches) == 3 and all(
        b["admitted"] <= b["dispatch_start"] for b in w.batches)


def test_engine_latency_is_fetch_end_minus_dispatch_start(index, rec):
    with RetrievalService(start=False,
                          batcher=MicroBatcher(max_batch=2)) as svc:
        svc.register("kb", index)
        t0 = time.perf_counter()
        svc.query(_queries(5), index="kb", k=5)
        svc.drain_once()
        samples = svc.engine("kb").latency.samples
        w = rec.window(t0, np.inf)
    dispatch, fetch = w.named("dispatch"), w.named("fetch")
    assert list(dispatch["batch"]) == list(fetch["batch"])
    assert list(samples) == list(fetch["end"] - dispatch["start"])
    assert len(samples) == 3


def test_drain_loop_counts_cycles_polls_and_gc(index, rec):
    with RetrievalService(poll_interval_s=0.005) as svc:
        svc.register("kb", index)
        svc.query(_queries(2), index="kb", k=5).result(timeout=30)
        time.sleep(0.05)
        gc.collect(2)
        svc.query(_queries(2), index="kb", k=5).result(timeout=30)
        svc.close()                 # joins the drain thread: counts settle
        s = svc.stats()
        typed = svc.stats_typed()
    assert s["drain_cycles"] >= 2 and s["poll_timeouts"] >= 1
    assert len(s["gc_collections"]) == 3 and s["gc_collections"][2] >= 1
    assert len(s["gc_pause_s"]) == 3 and s["gc_pause_s"][2] > 0
    assert typed.drain_cycles == s["drain_cycles"]
    assert len(rec.window(-np.inf, np.inf).named("poll_wait")) >= 1


def test_engine_accessor_returns_the_live_engine(index, rec):
    with RetrievalService(start=False) as svc:
        svc.register("kb", index)
        engine = svc.engine("kb")
        assert engine is svc._registry.get("kb").live_version().engine
        assert engine.recorder is rec
        with pytest.raises(KeyError):
            svc.engine("missing")


def test_concurrent_writers_lose_no_record(rec):
    """More writer threads than cores, switching every microsecond, write
    several times FLUSH_ROWS rows (so writers flush too) while another
    thread keeps flushing: every span lands once, with a unique id,
    nested in its own thread's span."""
    import os
    import sys
    n_threads = 2 * (os.cpu_count() or 4)
    per_thread = max(200, -(-3 * FLUSH_ROWS // n_threads))
    t0 = time.perf_counter()
    outer_ids = {}
    writing = threading.Event()
    writing.set()

    def flusher():
        while writing.is_set():
            rec.flush()

    def writer(t):
        with rec.span("drain") as outer:
            outer_ids[t] = outer.id
            for i in range(per_thread):
                with rec.span("admit", batch=t):
                    pass
                rec.request(t * per_thread + i, 1, time.perf_counter(),
                            0.0, 0.0, 0.0)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    flushing = threading.Thread(target=flusher)
    try:
        flushing.start()
        threads = [threading.Thread(target=writer, args=(t,))
                   for t in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads)
    finally:
        writing.clear()
        flushing.join(timeout=60)
        sys.setswitchinterval(old)
    w = rec.window(t0, np.inf)
    admits = w.named("admit")
    assert len(admits) == n_threads * per_thread
    assert len(w.requests) == n_threads * per_thread
    assert len(np.unique(w.spans["id"])) == len(w.spans)
    for t, oid in outer_ids.items():
        assert np.all(admits["parent"][admits["batch"] == t] == oid)
