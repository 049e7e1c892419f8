"""Arithmetic shared by the metric readers in ``bench/metrics/``.

Each reader is a file of its own that calls one of these; a reader returns
``None`` where the run holds nothing for it to read (no trace, no batch of
the kind it counts), and the harness then leaves the metric out.
"""

from __future__ import annotations

import numpy as np

from bench import work


def latencies_ms(run) -> np.ndarray:
    """Every request of the window, from its scheduled arrival; a request
    that failed or was refused counts as missing any limit (infinite)."""
    return np.asarray([np.inf if r.latency_s is None else 1e3 * r.latency_s
                       for r in run.requests], np.float64)


def latency_percentile(run, p: float):
    lat = latencies_ms(run)
    if lat.size == 0:
        return None
    value = float(np.percentile(lat, p))
    return value if np.isfinite(value) else None


def throughput_qps(run):
    """Query rows completed inside the window per second of it."""
    rows = sum(len(r.rows) for r in run.requests
               if r.done_s is not None and r.done_s <= run.window_s)
    return rows / run.window_s if run.window_s > 0 else None


def support_recall(run, k: int = 10):
    """Share of each served row's two planted supporting passages in its
    top ``k``, averaged over every row the window served."""
    hits, rows = 0, 0
    for r in run.requests:
        if r.ids is None:
            continue
        rel = run.relevant[r.rows]                   # (rows, 2)
        top = r.ids[:, :k]
        hits += int((top[:, None, :] == rel[:, :, None]).any(axis=2).sum())
        rows += rel.shape[0]
    return hits / (2.0 * rows) if rows else None


def batch_rows(run):
    """Query rows per micro-batch over the window, from the engine's
    counters."""
    a, b = run.counters["start"], run.counters["end"]
    batches = b["batches_served"] - a["batches_served"]
    if batches <= 0:
        return None
    return (b["queries_served"] - a["queries_served"]) / batches


def search_ms(run):
    """Median of the engine's per-batch search times (host clock around
    ``index.search`` and the blocking copy) recorded in the window."""
    if not run.batch_latency_s:
        return None
    return 1e3 * float(np.median(run.batch_latency_s))


def device_idle(run):
    """Share of the traced window in which no operation ran on the device."""
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)


def _traced_batches(run) -> tuple[int, int]:
    a, b = run.traced["start"], run.traced["stop"]
    return (b["batches_served"] - a["batches_served"],
            b["queries_served"] - a["queries_served"])


def exact_roofline(run):
    """Least time of the traced window's exact-scan batches over the
    device's busy time in it, in %."""
    if run.trace is None or run.work.get("ivf") or run.trace.busy_s <= 0:
        return None
    n_batches, n_rows = _traced_batches(run)
    if n_batches <= 0:
        return None
    w, pk = run.work, run.peaks
    args = (w["n_docs"], w["dim"], w["code_bytes"], w["in_dim"], w["k"])
    # least time is linear in the rows of a batch on each side of the
    # ridge; where even a full batch is bound by bytes, every batch is, and
    # the window's least time is that of its mean batch times the count
    _, bound = work.least_time(*work.exact_scan(run.max_batch, *args),
                               pk["int8_ops_per_s"], pk["hbm_bytes_per_s"])
    if bound != "bytes":
        return None
    t, _ = work.least_time(*work.exact_scan(n_rows / n_batches, *args),
                           pk["int8_ops_per_s"], pk["hbm_bytes_per_s"])
    return 100.0 * t * n_batches / run.trace.busy_s


def ivf_roofline(run):
    """Least time of the traced window's IVF batches over the device's busy
    time in it, in %.  Only where every request is exactly one micro-batch
    (blocks of the engine's ``max_batch`` rows), so that the traced
    requests are the traced batches."""
    if run.trace is None or not run.work.get("ivf") \
            or run.trace.busy_s <= 0 or "pool_probes" not in run.work \
            or run.traffic.rows != run.max_batch:
        return None
    n_batches, _ = _traced_batches(run)
    t0 = run.counters["start"]["t"]
    lo = run.traced["start"]["t"] - t0
    hi = run.traced["stop"]["t"] - t0
    blocks = [r for r in run.requests
              if r.done_s is not None and lo <= r.done_s <= hi]
    if n_batches <= 0 or not blocks:
        return None
    w, pk = run.work, run.peaks
    times = [work.least_time(*work.ivf_scan(
        w["pool_probes"][r.rows], w["list_lens"], w["dim"], w["code_bytes"],
        w["in_dim"], w["k"]), pk["int8_ops_per_s"], pk["hbm_bytes_per_s"])[0]
        for r in blocks]
    return 100.0 * float(np.mean(times)) * n_batches / run.trace.busy_s
