"""Spans and records of the serving path, on the profiler's clock.

One :class:`Recorder` per process (:func:`default_recorder`, the way
``jax.monitoring`` is process-wide) collects what the drain path does:

* **spans** — ``with recorder.span("dispatch", batch_id):`` enters a
  ``jax.profiler.TraceAnnotation("repro.dispatch")``, so whenever a trace
  is being recorded the span lands on the profiler's host plane, on the
  same clock as the device's operations; it also stamps
  ``time.perf_counter()`` at both ends into a ring of records, with the
  span's batch id and the id of the span it is nested in (same thread);
* **batch records** — one per micro-batch: its drain, valid and padded
  rows, the earliest admission among its rows and the form / dispatch /
  device-wait / fetch stamps;
* **request records** — one per resolved request: rows and its admitted /
  dispatched / done / resolved stamps;
* **garbage collection** — a ``gc.callbacks`` hook counts collections and
  pause seconds by generation, and turns every generation-2 collection,
  and any collection longer than :data:`GC_SLOW_S`, into a ``repro.gc``
  span.

The rings are preallocated numpy structured arrays of :data:`CAPACITY`
rows (enough for a 30 s window at 1,200 requests/s): live records are no
Python objects for the collector to traverse.  Rows wait as tuples only
until the next :meth:`Recorder.flush` (one batch's worth, on the serving
path).  :meth:`Recorder.window` returns the records of a time interval,
or ``None`` once a ring has overwritten one of them.
"""

from __future__ import annotations

import atexit
import gc
import itertools
import threading
import time
from typing import NamedTuple, Optional

import numpy as np
from jax.profiler import TraceAnnotation

PREFIX = "repro."
SPAN_NAMES = ("admit", "poll_wait", "drain", "form", "dispatch",
              "device_wait", "fetch", "scatter", "resolve", "gc", "flush")
NAME_ID = {name: i for i, name in enumerate(SPAN_NAMES)}
LABELS = {name: PREFIX + name for name in SPAN_NAMES}
CAPACITY = 1 << 17            # rows per ring
FLUSH_ROWS = 4096             # pending rows that make a writer flush
GC_SLOW_S = 1e-3              # a collection this long is a span at any gen

SPAN = np.dtype([("id", np.int64), ("name", np.int8),
                 ("start", np.float64), ("end", np.float64),
                 ("batch", np.int64), ("parent", np.int64)])
BATCH = np.dtype([("id", np.int64), ("drain", np.int64),
                  ("n_valid", np.int32), ("rows", np.int32),
                  ("admitted", np.float64),
                  ("form_start", np.float64), ("form_end", np.float64),
                  ("dispatch_start", np.float64),
                  ("dispatch_end", np.float64),
                  ("wait_end", np.float64), ("fetch_end", np.float64)])
REQUEST = np.dtype([("id", np.int64), ("rows", np.int32),
                    ("admitted", np.float64), ("dispatched", np.float64),
                    ("done", np.float64), ("resolved", np.float64)])


class Window(NamedTuple):
    """Records whose start lies in an interval, each sorted by start."""

    spans: np.ndarray           # SPAN rows, by "start"
    batches: np.ndarray         # BATCH rows, by "form_start"
    requests: np.ndarray        # REQUEST rows, by "admitted"

    def named(self, name: str) -> np.ndarray:
        """The window's spans called ``name`` (without the prefix)."""
        return self.spans[self.spans["name"] == NAME_ID[name]]


class _Ring:
    """Fixed-capacity ring of records; the owner serializes access.

    ``evicted`` is the latest start among the rows overwritten so far:
    rows reach the ring when they are flushed, not in start order, so a
    window is whole only if no overwritten row started inside it.
    """

    def __init__(self, dtype: np.dtype, capacity: int, key: str):
        self.rows = np.zeros(capacity, dtype)
        self.key = key
        self.written = 0
        self.evicted = -np.inf

    def extend(self, rows: list) -> None:
        if not rows:
            return
        new = np.array(rows, self.rows.dtype)
        cap = len(self.rows)
        at = self.written + np.arange(len(new))
        held = (at >= cap) & (at - cap < self.written)   # a row is there
        lost = np.concatenate([self.rows[self.key][at[held] % cap],
                               new[self.key][:-cap]])
        if lost.size:
            self.evicted = max(self.evicted, float(lost.max()))
        self.rows[at[-cap:] % cap] = new[-cap:]
        self.written += len(new)

    def select(self, t0: float, t1: float) -> Optional[np.ndarray]:
        if self.written > len(self.rows) and self.evicted >= t0:
            return None
        rows = self.rows[:min(self.written, len(self.rows))]
        start = rows[self.key]
        out = rows[(start >= t0) & (start < t1)]
        return out[np.argsort(out[self.key], kind="stable")]


class _Pending:
    """Rows written since the last flush, as tuples.  Writers only append
    to the three lists, which are never replaced (an append is atomic in
    CPython, so it is safe from any thread and inside a gc callback, and
    takes no lock).  :meth:`take` runs under the recorder's lock and
    removes just the rows it copied: a row appended meanwhile stays for
    the next flush."""

    def __init__(self):
        self.spans: list[tuple] = []
        self.batches: list[tuple] = []
        self.requests: list[tuple] = []

    def take(self) -> tuple[list, list, list]:
        return _take(self.spans), _take(self.batches), _take(self.requests)


def _take(rows: list) -> list:
    n = len(rows)
    taken = rows[:n]
    del rows[:n]
    return taken


class Span:
    """One open span (see :meth:`Recorder.span`).  ``start``/``end`` are
    ``perf_counter`` stamps, readable once the span has exited;
    ``batch`` may be set before it exits."""

    __slots__ = ("name", "batch", "id", "parent", "start", "end",
                 "_recorder", "_annotation", "_stack")

    def __init__(self, recorder: "Recorder", name: str, batch: int):
        self._recorder = recorder
        self.name = name
        self.batch = batch

    def __enter__(self) -> "Span":
        rec = self._recorder
        self._annotation = TraceAnnotation(LABELS[self.name])
        self._annotation.__enter__()
        self._stack = stack = rec._stack()
        self.parent = stack[-1] if stack else -1
        self.id = next(rec._span_ids)
        stack.append(self.id)
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.end = time.perf_counter()
        self._stack.pop()
        self._annotation.__exit__(None, None, None)
        self._recorder._append(
            self._recorder._pending.spans,
            (self.id, NAME_ID[self.name], self.start, self.end, self.batch,
             self.parent))
        return False


class _GcHook:
    """The ``gc.callbacks`` hook.  It runs in whichever thread collects,
    possibly inside a recorder flush, so it takes no lock: its spans go to
    the pending rows.  Only generation-2 collections are annotated on the
    profiler's plane (the generation is known when one starts).
    Collections never nest, so one slot holds the open one."""

    def __init__(self, local: threading.local, pending: _Pending,
                 span_ids: itertools.count):
        self.collections = [0, 0, 0]
        self.pause_s = [0.0, 0.0, 0.0]
        self._local = local
        self._pending = pending
        self._span_ids = span_ids
        self._annotation = None
        self._t0 = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            if info["generation"] == 2:
                self._annotation = TraceAnnotation(LABELS["gc"])
                self._annotation.__enter__()
            self._t0 = time.perf_counter()
            return
        t1 = time.perf_counter()
        if self._t0 is None:              # attached mid-collection
            return
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None
        gen, t0 = info["generation"], self._t0
        self._t0 = None
        self.collections[gen] += 1
        self.pause_s[gen] += t1 - t0
        if gen == 2 or t1 - t0 > GC_SLOW_S:
            stack = getattr(self._local, "stack", None)
            self._pending.spans.append((next(self._span_ids), NAME_ID["gc"],
                                        t0, t1, -1,
                                        stack[-1] if stack else -1))


class Recorder:
    """Spans, batch and request records of the serving path (module doc).

    A write appends a tuple to a pending list, lock-free; :meth:`flush`
    moves the pending rows into the rings in bulk under one lock.  The
    engine flushes right after it dispatches a batch, while the device
    computes (a ``flush`` span, so its share of ``search_ms`` can be
    read); a writer that finds :data:`FLUSH_ROWS` rows pending flushes
    itself, and :meth:`window` flushes before it reads.
    """

    def __init__(self, capacity: int = CAPACITY):
        self._mu = threading.Lock()
        self._spans = _Ring(SPAN, capacity, "start")
        self._batches = _Ring(BATCH, capacity, "form_start")
        self._requests = _Ring(REQUEST, capacity, "admitted")
        self._pending = _Pending()
        self._local = threading.local()
        self._span_ids = itertools.count()
        self._batch_ids = itertools.count()
        self._gc = _GcHook(self._local, self._pending, self._span_ids)
        gc.callbacks.append(self._gc)

    def close(self) -> None:
        """Detach the garbage-collection hook."""
        if self._gc in gc.callbacks:
            gc.callbacks.remove(self._gc)

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    # -- writers -----------------------------------------------------------
    def span(self, name: str, batch: int = -1) -> Span:
        """Context manager: a ``repro.<name>`` span (``name`` one of
        :data:`SPAN_NAMES`), nested in the thread's open span."""
        return Span(self, name, batch)

    def next_batch(self) -> int:
        return next(self._batch_ids)

    def _append(self, pending: list, row: tuple) -> None:
        pending.append(row)
        if len(pending) >= FLUSH_ROWS:
            self.flush()

    def batch(self, batch_id: int, drain: int, n_valid: int, rows: int,
              admitted: float, form: Span, dispatch: Span, wait: Span,
              fetch: Span) -> None:
        """One micro-batch, from the spans that carried it."""
        self._append(self._pending.batches, (
            batch_id, drain, n_valid, rows, admitted, form.start, form.end,
            dispatch.start, dispatch.end, wait.end, fetch.end))

    def request(self, request_id: int, rows: int, admitted: float,
                dispatched: float, done: float, resolved: float) -> None:
        self._append(self._pending.requests, (
            request_id, rows, admitted, dispatched, done, resolved))

    def flush(self) -> None:
        """Move the pending rows into the rings."""
        with self._mu:
            spans, batches, requests = self._pending.take()
            self._spans.extend(spans)
            self._batches.extend(batches)
            self._requests.extend(requests)

    # -- readers -----------------------------------------------------------
    def window(self, t0: float, t1: float) -> Optional[Window]:
        """Records whose start lies in ``[t0, t1)``, or ``None`` if a ring
        has overwritten one of them."""
        self.flush()
        with self._mu:
            parts = (self._spans.select(t0, t1),
                     self._batches.select(t0, t1),
                     self._requests.select(t0, t1))
        if any(p is None for p in parts):
            return None
        return Window(*parts)

    def gc_counts(self) -> tuple[tuple[int, ...], tuple[float, ...]]:
        """(collections, pause seconds), each by generation 0, 1, 2."""
        return tuple(self._gc.collections), tuple(self._gc.pause_s)


_default: Optional[Recorder] = None
_default_mu = threading.Lock()


def default_recorder() -> Recorder:
    """The process-wide recorder (made, and its gc hook attached, on the
    first call; the hook is detached at exit, before modules are torn
    down)."""
    global _default
    with _default_mu:
        if _default is None:
            _default = Recorder()
            atexit.register(_default.close)
        return _default
