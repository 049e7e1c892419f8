"""The output check: what the window served against the plain reference.

A sample of the window's requests, drawn from the seed, is compared row by
row with :class:`bench.reference.Reference`.  The numbers, each held to the
limit that its configuration file gives:

``lost``
    requests admitted in the window that never came back (limit 0).
``malformed``
    sampled rows whose answer is not k distinct ids of the corpus with
    finite scores in descending order (limit 0).
``score_err``
    the largest gap between the score served for an id and the reference's
    score of that id for that query.  It catches a wrong query encoding, a
    wrong kernel, and an answer handed to the wrong request.
``rank_gap``
    the largest amount by which the reference score of the r-th served id
    lies below the reference's r-th best score (exact search only).
``top1_miss``
    the share of rows whose reference best passage is not in the served
    top k (approximate search only).  The lists nearest a query hold its
    best passage; routing to the wrong lists misses it as often as it
    leaves that list out.

Every number must stay at or under its limit.
"""

from __future__ import annotations

import numpy as np

def sample_requests(requests, rows_wanted: int, rng: np.random.Generator):
    """Requests that came back, drawn from ``rng`` until they hold
    ``rows_wanted`` rows (all of them if they hold fewer)."""
    served = [r for r in requests if r.ids is not None]
    order = rng.permutation(len(served))
    picked, rows = [], 0
    for i in order:
        if rows >= rows_wanted:
            break
        picked.append(served[i])
        rows += len(served[i].rows)
    return picked


def malformed_rows(ids: np.ndarray, scores: np.ndarray, k: int,
                   n_docs: int) -> np.ndarray:
    """Per row: True where the answer is not well formed."""
    if ids.shape[1] != k:
        return np.ones(ids.shape[0], bool)
    bad = ((ids < 0) | (ids >= n_docs)).any(axis=1)
    bad |= ~np.isfinite(scores).all(axis=1)
    bad |= (np.diff(scores, axis=1) > 0).any(axis=1)
    s = np.sort(ids, axis=1)
    bad |= (s[:, 1:] == s[:, :-1]).any(axis=1)
    return bad


def compare(queries: np.ndarray, ids: np.ndarray, scores: np.ndarray,
            reference, k: int, numbers) -> dict[str, float]:
    """The check's numbers for served ``ids``/``scores`` of ``queries``."""
    out: dict[str, float] = {}
    bad = malformed_rows(ids, scores, k, reference.n_docs)
    out["malformed"] = float(bad.sum())
    ok = ~bad
    q, ids, scores = queries[ok], ids[ok], scores[ok]
    want = {"score_err", "rank_gap", "top1_miss"} & set(numbers)
    if want and q.shape[0]:
        ref_of_served = reference.scores_of(q, ids)
        if "score_err" in want:
            out["score_err"] = float(np.max(np.abs(scores - ref_of_served)))
        if want & {"rank_gap", "top1_miss"}:
            best, best_ids = reference.topk(q, k)
            if "rank_gap" in want:
                out["rank_gap"] = float(max(0.0,
                                            np.max(best - ref_of_served)))
            if "top1_miss" in want:
                found = (ids == best_ids[:, :1]).any(axis=1)
                out["top1_miss"] = float(1.0 - found.mean())
    return {n: v for n, v in out.items() if n in numbers}


def verdict(values: dict[str, float], limits: dict[str, float]) -> bool:
    """Correct when every number is within its limit."""
    return all(np.isfinite(v := values.get(n, float("nan"))) and v <= limits[n]
               for n in limits)


def report(values: dict[str, float], limits: dict[str, float]) -> dict:
    """``{name: {"value": v, "limit": l}}`` in the order of the limits."""
    return {n: {"value": values.get(n, float("nan")), "limit": limits[n]}
            for n in limits}


def set_limit(sound: float, broken: float) -> float | None:
    """A limit between the worst sound reading and the least broken one
    (the control's or a planted fault's), or ``None`` where they are not
    three times apart.  The limit sits a third of the way from the broken
    reading to the sound one in log scale, so that there is more room on
    the sound side: fresh seeds read worse than the ones seen."""
    if broken < 3 * sound or sound <= 0:
        return None
    return float(sound ** (1 / 3) * broken ** (2 / 3))
