"""Find the knee of an open-loop cell: the highest offered rate it keeps up
with, with no growing backlog and its tail held.

    python bench/sweep.py --workload <cell> --seed <n> --rates r1,r2,...
        [--seconds <run_seconds>]

One set-up, then one window per rate (requests per second) with the cell's
traffic at that rate, each as long as a run's window unless ``--seconds``
says otherwise.  A rate is kept up with when no request is refused or lost,
the rows served per second of the window reach 97% of those offered, the
median and the 95th percentile of the last fifth of the requests are each
under twice those of the first fifth (a backlog that grows through the
window shows there), and the window's 95th percentile is under twice its
median: a request that keeps up waits at most for the batch in flight
before its own.  The knee is the highest rate that, with every lower one,
was kept up with.  The table goes to standard output, one JSON line per
rate, and a last line names the knee.  The cell's fixed rate is written
into its traffic file by hand, as a number.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

KEEP_UP = 0.97
GROWTH = 2.0


def verdict(requests, window_s: float, rate: float) -> dict:
    import numpy as np
    done = [r for r in requests if r.latency_s is not None]
    lat = np.asarray([r.latency_s for r in done]) * 1e3
    n = len(done)
    fifth = max(1, n // 5)

    def pct(x, q):
        return float(np.percentile(x, q)) if len(x) else float("nan")

    head, tail = lat[:fifth], lat[-fifth:]
    last = max((r.done_s for r in done), default=window_s)
    served = sum(len(r.rows) for r in done) / max(window_s, last)
    offered = sum(len(r.rows) for r in requests) / window_s
    row = {"rate": rate, "offered_rows_per_s": offered,
           "served_rows_per_s": served, "requests": len(requests),
           "failed": len(requests) - n,
           "p50_ms": pct(lat, 50), "p95_ms": pct(lat, 95),
           "first_fifth_p50_ms": pct(head, 50),
           "last_fifth_p50_ms": pct(tail, 50),
           "first_fifth_p95_ms": pct(head, 95),
           "last_fifth_p95_ms": pct(tail, 95)}
    row["keeps_up"] = bool(
        n == len(requests) and n > 0 and served >= KEEP_UP * offered
        and row["last_fifth_p50_ms"] < GROWTH * row["first_fifth_p50_ms"]
        and row["last_fifth_p95_ms"] < GROWTH * row["first_fifth_p95_ms"]
        and row["p95_ms"] < GROWTH * row["p50_ms"])
    return row


def knee_rate(rows: list[dict]):
    """The highest swept rate that, with every lower one, kept up.  A
    saturated service can pass the tests again far above its knee, when
    queueing dominates every latency alike."""
    knee = None
    for row in sorted(rows, key=lambda r: r["rate"]):
        if not row["keeps_up"]:
            break
        knee = row["rate"]
    return knee


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window per rate (default: run_seconds)")
    args = ap.parse_args(argv)

    from bench.startup import configure_jax
    configure_jax()

    from bench import catalog, harness

    cell = catalog.load_cell(args.workload)
    seconds = args.seconds or float(catalog.load_benchmark()["run_seconds"])
    if cell.traffic.loop != "open":
        ap.error("a knee is swept for an open-loop cell")
    session = harness.Session(cell, args.seed, t_start=T_START)
    rows = []
    try:
        for rate in sorted(float(r) for r in args.rates.split(",")):
            traffic = dataclasses.replace(cell.traffic, rate=rate)
            run = session.window(seconds, False, t_start=T_START,
                                 traffic=traffic)
            rows.append(verdict(run.requests, run.window_s, rate))
            print(json.dumps(rows[-1]), flush=True)
    finally:
        session.close()
    knee = knee_rate(rows)
    print(json.dumps({"workload": cell.name, "knee_rate": knee,
                      "rate_at_0.8_knee": None if knee is None
                      else 0.8 * knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
