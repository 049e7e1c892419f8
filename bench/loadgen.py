"""Traffic: an open-loop arrival schedule and a closed loop of clients.

The open loop is the schedule and collector of the repository's
``benchmarks/loadgen.py`` (``build_workload`` with ``arrival="poisson"`` and
``run_trial``'s collection), copied here so that the yardstick does not move
when that file does.  Arrivals are drawn up front; the submitter fires each
request at its scheduled instant whether or not earlier ones came back, and a
request's latency runs from its *scheduled* arrival to the moment its last
micro-batch completed: the submitter's own lag plus ``ServeResult.latency_s``.
Queueing under load is measured, not hidden (no coordinated omission).

The closed loop is a fixed number of clients, each sending its next block
only when the previous one came back, as batch jobs over a dev set do.

Both loops take rows from a pool of held-out queries, drawn from the seed.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, Optional

import numpy as np


@dataclasses.dataclass
class Request:
    """One request of the window, as the client saw it."""

    rows: np.ndarray                   # pool indices, one per query row
    sent_s: float                      # scheduled (open) or sent (closed)
    done_s: Optional[float] = None     # completion, from the window's start
    latency_s: Optional[float] = None  # from scheduled arrival to done
    ids: Optional[np.ndarray] = None
    scores: Optional[np.ndarray] = None
    error: Optional[str] = None


@dataclasses.dataclass
class Traffic:
    """A traffic mix, as its ``bench/traffic/<name>.json`` file gives it."""

    name: str
    loop: str                    # "open" | "closed"
    rows: int                    # query rows per request
    k: int
    pool: int                    # held-out queries the rows are drawn from
    rate: float = 0.0            # open loop: requests per second
    clients: int = 0             # closed loop: concurrent clients
    sample_rows: int = 512       # rows the output check compares

    @classmethod
    def from_dict(cls, name: str, d: dict) -> "Traffic":
        fields = {f.name for f in dataclasses.fields(cls)} - {"name"}
        unknown = set(d) - fields
        if unknown:
            raise ValueError(f"traffic {name!r}: unknown keys {sorted(unknown)}")
        t = cls(name=name, **d)
        if t.loop == "open" and t.rate <= 0:
            raise ValueError(f"traffic {name!r}: an open loop needs rate > 0")
        if t.loop == "closed" and t.clients < 1:
            raise ValueError(f"traffic {name!r}: a closed loop needs clients")
        if t.loop not in ("open", "closed"):
            raise ValueError(f"traffic {name!r}: loop is open or closed")
        return t

    def warm_rows(self, max_batch: int) -> list[int]:
        """Every micro-batch row count this traffic can make a batcher of
        ``max_batch`` rows form: its power-of-two buckets up to
        ``max_batch``.  A closed loop of blocks of a multiple of
        ``max_batch`` rows only ever forms full batches."""
        if self.loop == "closed" and self.rows % max_batch == 0:
            return [max_batch]
        sizes, b = [], 1
        while b < max_batch:
            sizes.append(b)
            b *= 2
        return sizes + [max_batch]


def poisson_schedule(rng: np.random.Generator, rate: float,
                     seconds: float) -> np.ndarray:
    """Arrival instants in [0, seconds) of a Poisson process of ``rate``."""
    n = int(rate * seconds * 1.2) + 16
    t = np.cumsum(rng.exponential(1.0 / rate, size=n))
    while t[-1] < seconds:
        t = np.concatenate([t, t[-1] + np.cumsum(
            rng.exponential(1.0 / rate, size=n))])
    return t[t < seconds]


def open_loop(submit: Callable, pool_rows: Callable[[np.ndarray], np.ndarray],
              traffic: Traffic, rng: np.random.Generator, seconds: float,
              *, timeout_s: float = 60.0, mark: Callable = None
              ) -> tuple[list[Request], float]:
    """Fire the schedule; returns (requests, measured window seconds).

    ``submit(block)`` returns a handle with ``result(timeout)``;
    ``pool_rows(indices)`` gives the query rows.  ``mark(name)`` is a
    context manager factory for host spans around submit and collect.
    """
    arrivals = poisson_schedule(rng, traffic.rate, seconds)
    picks = rng.integers(0, traffic.pool, size=(len(arrivals), traffic.rows))
    blocks = [pool_rows(p) for p in picks]      # no host work on the clock
    records, handles = [], []
    t0 = time.perf_counter()
    for i, sched in enumerate(arrivals):
        lag = sched - (time.perf_counter() - t0)
        if lag > 0:
            time.sleep(lag)
        req = Request(rows=picks[i], sent_s=float(sched))
        submitted = time.perf_counter() - t0
        try:
            with mark("bench.submit"):
                h = submit(blocks[i])
        except Exception as e:            # refused: counts as failed
            req.error = f"{type(e).__name__}: {e}"
            h = None
        records.append(req)
        handles.append((h, submitted))
    window = max(seconds, time.perf_counter() - t0)
    with mark("bench.collect"):
        for req, (h, submitted) in zip(records, handles):
            if h is None:
                continue
            try:
                res = h.result(timeout=timeout_s)
            except Exception as e:
                req.error = f"{type(e).__name__}: {e}"
                continue
            req.latency_s = (submitted - req.sent_s) + res.latency_s
            req.done_s = req.sent_s + req.latency_s
            req.ids, req.scores = res.ids, res.scores
    return records, window


def closed_loop(submit: Callable, pool_rows: Callable[[np.ndarray], np.ndarray],
                traffic: Traffic, rng: np.random.Generator, seconds: float,
                *, timeout_s: float = 60.0, mark: Callable = None
                ) -> tuple[list[Request], float]:
    """``traffic.clients`` threads, each sending a block and waiting for it,
    until the window closes.  Returns (requests, window seconds); requests
    still in flight at the close are waited for and kept, with their
    completion time, so the caller can tell them apart."""
    seeds = rng.integers(0, 2 ** 63 - 1, size=traffic.clients)
    per_client: list[list[Request]] = [[] for _ in range(traffic.clients)]
    t0 = time.perf_counter()

    def client(c: int) -> None:
        crng = np.random.default_rng(int(seeds[c]))
        while time.perf_counter() - t0 < seconds:
            picks = crng.integers(0, traffic.pool, size=traffic.rows)
            block = pool_rows(picks)
            req = Request(rows=picks, sent_s=time.perf_counter() - t0)
            try:
                with mark("bench.submit"):
                    h = submit(block)
                with mark("bench.collect"):
                    res = h.result(timeout=timeout_s)
            except Exception as e:
                req.error = f"{type(e).__name__}: {e}"
                per_client[c].append(req)
                return
            req.done_s = time.perf_counter() - t0
            req.latency_s = req.done_s - req.sent_s
            req.ids, req.scores = res.ids, res.scores
            per_client[c].append(req)

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(traffic.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=seconds + timeout_s + 30.0)
    if any(t.is_alive() for t in threads):
        raise RuntimeError("a closed-loop client did not finish")
    return [r for reqs in per_client for r in reqs], float(seconds)
