"""Arithmetic of the readers that read the program's own spans and records.

The serving path writes them into the process-wide recorder
(``repro.serve.trace.default_recorder``): a span per step of the drain
loop, a record per micro-batch and per resolved request, each with
``time.perf_counter`` stamps, the clock of ``run.counters[...]["t"]``.
Each function here returns ``None`` where the run holds nothing for it to
read: a program without that recorder, a window the recorder's rings no
longer hold whole, or no record of the kind it reads.
"""

from __future__ import annotations

import numpy as np


def records(run):
    """The recorder's records whose start lies in the run's window."""
    try:
        from repro.serve.trace import default_recorder
    except ImportError:
        return None
    if "start" not in run.counters or "end" not in run.counters:
        return None
    return default_recorder().window(run.counters["start"]["t"],
                                     run.counters["end"]["t"])


def _median_ms(values: np.ndarray):
    values = values[np.isfinite(values)]
    return 1e3 * float(np.median(values)) if values.size else None


def queue_wait_ms(window):
    """Median over the window's requests of admission → the dispatch of
    the first batch that carried them."""
    if window is None:
        return None
    r = window.requests
    return _median_ms(r["dispatched"] - r["admitted"])


def turnaround_ms(window):
    """Median over the window's batches that had work waiting (a row
    admitted before the previous batch's fetch ended) of the previous
    batch's fetch end → this batch's dispatch start."""
    if window is None or len(window.batches) < 2:
        return None
    b = np.sort(window.batches, order="dispatch_start")
    prev_end, cur = b["fetch_end"][:-1], b[1:]
    waited = cur["admitted"] < prev_end
    return _median_ms(cur["dispatch_start"][waited] - prev_end[waited])


def dispatch_ms(window):
    """Median ``repro.dispatch`` span: ``index.search`` called until it
    returns (query copy-in, jit dispatch, host-side work of the search)."""
    if window is None:
        return None
    d = window.named("dispatch")
    return _median_ms(d["end"] - d["start"])
