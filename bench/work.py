"""The least work of a search, counted from the operation itself.

These functions count what any implementation of the operation has to do,
not what today's implementation does: a roofline share measured against
them does not move when a later change fuses a step or restructures a grid.

* Exact scan of ``q`` query rows: one read of every stored code
  (``n_docs × code_bytes``), the float query rows in and the (q, k) scores
  and ids out; ``2 · q · n_docs · dim`` integer multiply-adds.
* IVF scan of one batch: one read of each *distinct* inverted list that the
  batch's rows probe, at its true length (padding is not work), plus the
  queries and results; ``2 · dim`` operations for every (row, probed list,
  list row).
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

RESULT_BYTES = 8            # one float32 score + one int32 id


def exact_scan(q_rows: int, n_docs: int, dim: int, code_bytes: int,
               in_dim: int, k: int) -> tuple[float, float]:
    """(operations, bytes) of an exact scan for ``q_rows`` queries."""
    ops = 2.0 * q_rows * n_docs * dim
    nbytes = (float(n_docs) * code_bytes + q_rows * in_dim * 4.0
              + q_rows * k * RESULT_BYTES)
    return ops, nbytes


def ivf_scan(probes: np.ndarray, list_lens: np.ndarray, dim: int,
             code_bytes: int, in_dim: int, k: int) -> tuple[float, float]:
    """(operations, bytes) of one IVF batch; ``probes`` is (rows, nprobe)
    list ids, ``list_lens`` the true length of every list."""
    probes = np.asarray(probes)
    lens = np.asarray(list_lens, np.float64)
    distinct = np.unique(probes)
    ops = 2.0 * dim * float(lens[probes].sum())
    q_rows = probes.shape[0]
    nbytes = (float(lens[distinct].sum()) * code_bytes
              + q_rows * in_dim * 4.0 + q_rows * k * RESULT_BYTES)
    return ops, nbytes


def least_time(ops: float, nbytes: float, peak_ops: float,
               bytes_per_s: float) -> tuple[float, str]:
    """(seconds, "bytes" | "ops"): the larger of the two bounds."""
    t_bytes, t_ops = nbytes / bytes_per_s, ops / peak_ops
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")


def peaks(device_kind: str, path: Path | None = None) -> dict:
    """The peaks of one chip by its ``device_kind``; an unknown kind is an
    error, never a default."""
    path = path or Path(__file__).resolve().parent / "peaks.json"
    table = json.loads(path.read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]
