"""CI perf-regression gate: measure the quick benches, compare, fail loud.

    PYTHONPATH=src:. python benchmarks/ci_gate.py                 # gate
    PYTHONPATH=src:. python benchmarks/ci_gate.py --write-baseline

Measures serving-shaped workloads on a 100k-doc clustered synthetic KB
(the regime IVF exists for): per gated backend (int8 and 1-bit) an exact
quantized search and an IVF search over the same storage, plus a
mid-traffic live-update cycle.  Writes ``BENCH_<git-sha>.json`` with
throughput (qps), per-request latency percentiles (p50/p99 ms), and IVF
recall@10 against the backend's own exact ranking.  The measurement is
then checked two ways:

* **absolute invariants** — IVF must beat exact search in qps on every
  gated backend *while* holding ``recall@10 >= 0.80`` (the fused-IVF PR's
  acceptance bar; machine-independent, no baseline needed),
* **baseline comparison** against the committed
  ``benchmarks/BENCH_baseline.json`` — throughput/latency may not regress
  more than ``--tolerance`` (default 20%), recall not more than
  ``--recall-tolerance`` (absolute).

Any violation exits non-zero, which fails the CI job; the fresh JSON is
uploaded as a workflow artifact either way, so the perf trajectory is
recorded per commit.  ``--write-baseline`` records the current machine's
measurement with ``--slack`` headroom folded in (CI runners are noisy;
the committed floor should be conservative, the tolerance strict).
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

import threading

from repro.data import make_dpr_like_kb
from repro.retrieval import (IndexSpec, build_index, load_index,
                             load_index_meta, recall_at_k, save_index)
from repro.serve import AdaptiveBatcher, MicroBatcher, QueryOptions, \
    RetrievalService, ServeEngine

HERE = os.path.dirname(os.path.abspath(__file__))
DEFAULT_BASELINE = os.path.join(HERE, "BENCH_baseline.json")

#: backends the gate measures: name → IndexSpec method (the 1-bit lane
#: runs through the learned rotation, which is what buys its recall)
GATE_BACKENDS = {"int8": "pca_int8", "onebit": "pca_rot_onebit"}

#: absolute floor on IVF recall@10 vs the backend's own exact ranking —
#: IVF must stay a *good* index, not merely a fast one
RECALL_FLOOR = 0.80

#: the serving SLO row, machine-independent by construction: the threaded
#: front door (admission control + micro-batching + async handles) must
#: sustain at least this fraction of the bare exact engine's qps on the
#: same index, same machine — a ratio, so runner speed cancels out
SERVICE_RATIO_FLOOR = 0.40

#: tiered-storage row, also a ratio: serving the chunked artifact with a
#: 5% hot-tier budget (encoded lists 20× bigger than the budget) must
#: sustain at least this fraction of the fully-resident qps under
#: Zipf-skewed traffic — the cold tier may cost, not collapse
TIERED_RATIO_FLOOR = 0.25

#: metric name → direction ("higher" is better, or "lower")
METRICS = {
    "exact_qps_int8": "higher", "ivf_qps_int8": "higher",
    "ivf_p50_ms_int8": "lower", "ivf_p99_ms_int8": "lower",
    "ivf_recall_at_10_int8": "recall",
    "exact_qps_onebit": "higher", "ivf_qps_onebit": "higher",
    "ivf_p50_ms_onebit": "lower", "ivf_p99_ms_onebit": "lower",
    "ivf_recall_at_10_onebit": "recall",
    "update_qps": "higher",
    "service_qps": "higher",
    "service_exact_ratio": "higher",
    "service_p99_ms": "lower",
    "tiered_qps_full": "higher",
    "tiered_qps_cold": "higher",
    "tiered_cold_ratio": "higher",
    "sharded_qps": "higher",
    "sharded_parity": "recall",
    "sharded_lost_requests": "lower",
}


def git_sha() -> str:
    try:
        out = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             capture_output=True, text=True,
                             cwd=HERE, timeout=10)
        if out.returncode == 0:
            return out.stdout.strip()
    except OSError:
        pass
    return "nogit"


def serve_rounds(engine, queries, n_requests, batch, warmup: int = 3):
    """Stream ``n_requests`` blocks through submit/drain; returns
    (qps, p50_ms, p99_ms).  ``warmup`` untimed rounds first, so jit
    compiles never land inside the measured window."""
    for _ in range(warmup):
        engine.submit(queries[:batch])
        engine.drain()
    lat = []
    n_rows = 0
    t0 = time.perf_counter()
    for r in range(n_requests):
        off = (r * batch) % (len(queries) - batch)
        engine.submit(queries[off: off + batch])
        n_rows += batch
        for res in engine.drain().values():
            lat.append(res.latency_s)
    wall = time.perf_counter() - t0
    ms = np.asarray(lat) * 1000.0
    return (n_rows / wall, float(np.percentile(ms, 50)),
            float(np.percentile(ms, 99)))


def serve_service(index, queries, n_requests, batch, k,
                  n_threads: int = 4):
    """Stream the same request load through the RetrievalService front
    door (threaded producers, background dispatcher, admission control).
    Returns (qps, request_p99_ms, lost, cache_identical).

    Throughput runs with the result cache OFF so every row really hits
    the engine; cache bit-identity is then checked separately on a
    cache-enabled service over the same index.
    """
    svc = RetrievalService(default_k=k,
                           batcher=AdaptiveBatcher(min_batch=8,
                                                   max_batch=64))
    svc.register("kb", index)
    for _ in range(3):                         # compile outside the window
        svc.query(queries[:batch], index="kb").result(timeout=300)
    per_thread = max(1, n_requests // n_threads)

    def producer(t):
        for r in range(per_thread):
            off = ((t * per_thread + r) * batch) % (len(queries) - batch)
            svc.query(queries[off: off + batch],
                      QueryOptions(index="kb")).result(timeout=300)

    threads = [threading.Thread(target=producer, args=(t,))
               for t in range(n_threads)]
    t0 = time.perf_counter()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    wall = time.perf_counter() - t0
    stats = svc.stats()
    svc.close()
    lost = (stats["requests_submitted"] - stats["requests_served"]
            + stats["queue_depth"])
    qps = per_thread * n_threads * batch / wall

    cached = RetrievalService(default_k=k, cache_rows=4096,
                              batcher=AdaptiveBatcher(min_batch=8,
                                                      max_batch=64))
    cached.register("kb", index)
    probe = queries[:batch] + 0.125            # never seen above: a miss
    first = cached.query(probe, index="kb").result(timeout=300)
    again = cached.query(probe, index="kb")
    identical = (again.done()                  # hit resolves at submit
                 and np.array_equal(first.scores, again.result().scores)
                 and np.array_equal(first.ids, again.result().ids))
    cached.close()
    return qps, stats["request_p99_ms"], lost, identical


def measure(n_docs: int, n_requests: int, batch: int, k: int,
            repeats: int, nlist: int, nprobe: int) -> dict:
    """One full measurement pass; best-of-``repeats`` per metric to damp
    scheduler noise.

    The corpus is the *clustered* synthetic (topical low-rank structure,
    like real DPR embeddings) at serving scale — coarse routing has
    something to find, and the exact scan is expensive enough that IVF's
    candidate pruning shows up as throughput, not noise.
    """
    kb = make_dpr_like_kb(n_queries=max(256, 2 * batch), n_docs=n_docs,
                          d=256, r_eff=48)
    queries = np.asarray(kb.queries)

    out = {"update_qps": 0.0}
    pairs = {}
    for bname, method in GATE_BACKENDS.items():
        exact = build_index(
            IndexSpec(method=method, dim=128, backend="jnp", post=False),
            kb.docs, kb.queries[:256])
        ivf = build_index(
            IndexSpec(method=method, dim=128, backend="jnp", post=False,
                      ivf=(nlist, nprobe), kmeans_iters=8,
                      kmeans_init="++", balanced_lists=True),
            kb.docs, kb.queries[:256])
        pairs[bname] = (exact, ivf)
        # recall@k: IVF at the gate probe width vs the backend's own
        # exact ranking (IVF loss isolated from compression loss)
        _, want = exact.search(kb.queries[:128], 10)
        _, got = ivf.search(kb.queries[:128], 10)
        out[f"ivf_recall_at_10_{bname}"] = recall_at_k(
            np.asarray(got), np.asarray(want))
        out[f"exact_qps_{bname}"] = 0.0
        out[f"ivf_qps_{bname}"] = 0.0
        out[f"ivf_p50_ms_{bname}"] = np.inf
        out[f"ivf_p99_ms_{bname}"] = np.inf

    mutable = build_index(
        IndexSpec(method="pca_int8", dim=128, backend="jnp", post=False,
                  mutable=True), kb.docs, kb.queries[:256])

    extra = np.asarray(kb.docs[:256])
    for _ in range(repeats):
        for bname, (exact, ivf) in pairs.items():
            e = ServeEngine(exact, k=k, batcher=MicroBatcher(max_batch=64))
            qps, _, _ = serve_rounds(e, queries, n_requests, batch)
            out[f"exact_qps_{bname}"] = max(out[f"exact_qps_{bname}"], qps)

            e = ServeEngine(ivf, k=k, batcher=MicroBatcher(max_batch=64))
            qps, p50, p99 = serve_rounds(e, queries, n_requests, batch)
            out[f"ivf_qps_{bname}"] = max(out[f"ivf_qps_{bname}"], qps)
            out[f"ivf_p50_ms_{bname}"] = min(out[f"ivf_p50_ms_{bname}"], p50)
            out[f"ivf_p99_ms_{bname}"] = min(out[f"ivf_p99_ms_{bname}"], p99)

        # live-update cycle: search throughput with a live delta segment
        # and tombstones layered on.  compact() hands each repeat a fresh
        # fold of the same corpus, so every repeat measures the identical
        # workload (segments/tombstones never accumulate across repeats).
        m = mutable.compact()
        first = m.next_gid
        m.add(extra)
        m.delete(range(first, first + len(extra) // 2))
        e = ServeEngine(m, k=k, batcher=MicroBatcher(max_batch=64))
        qps, _, _ = serve_rounds(e, queries, n_requests, batch)
        out["update_qps"] = max(out["update_qps"], qps)

    # the SLO row: the threaded front door over the int8 exact index,
    # measured against that index's bare-engine qps from the loop above
    out["service_qps"] = 0.0
    out["service_p99_ms"] = np.inf
    out["service_lost_requests"] = 0.0
    out["service_cache_identical"] = 1.0
    for _ in range(repeats):
        qps, p99, lost, identical = serve_service(
            pairs["int8"][0], queries, n_requests, batch, k)
        out["service_qps"] = max(out["service_qps"], qps)
        out["service_p99_ms"] = min(out["service_p99_ms"], p99)
        out["service_lost_requests"] += float(lost)
        out["service_cache_identical"] = min(
            out["service_cache_identical"], 1.0 if identical else 0.0)
    out["service_exact_ratio"] = out["service_qps"] / \
        max(out["exact_qps_int8"], 1e-9)

    # the tiered-storage row: the int8 IVF index streamed to a chunked
    # artifact, served fully resident vs at a 5% hot-tier budget, under
    # Zipf-skewed traffic (what the LRU hot tier exists for).  A ratio,
    # so runner speed cancels out.
    from benchmarks.loadgen import zipf_weights
    out["tiered_qps_full"] = 0.0
    out["tiered_qps_cold"] = 0.0
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "kb.v3")
        save_index(pairs["int8"][1], path, chunked=True)
        enc = load_index_meta(path)["encoded_nbytes"]
        rng = np.random.default_rng(7)
        qz = queries[rng.choice(len(queries), size=len(queries),
                                p=zipf_weights(len(queries), 1.1))]
        for _ in range(repeats):
            for key, resident in (("tiered_qps_full", "all"),
                                  ("tiered_qps_cold", enc // 20)):
                e = ServeEngine(load_index(path, resident=resident), k=k,
                                batcher=MicroBatcher(max_batch=64))
                qps, _, _ = serve_rounds(e, qz, n_requests, batch)
                out[key] = max(out[key], qps)
    out["tiered_cold_ratio"] = out["tiered_qps_cold"] / \
        max(out["tiered_qps_full"], 1e-9)

    out.update(sharded_row())
    return out


def sharded_row() -> dict:
    """The sharded-serving row: run ``sharded_bench.py --quick`` in a
    subprocess with forced host devices (``XLA_FLAGS`` must land before
    jax initialises, which this process is long past) and collect its
    gate JSON — bit-parity vs single-host across all four scorer
    backends, sharded throughput, and the zero-lost-requests count.

    The child is a CPU lane by design: on a machine with an accelerator
    it must not contend for the device this process holds."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    with tempfile.TemporaryDirectory() as tmp:
        gate = os.path.join(tmp, "sharded.json")
        cmd = [sys.executable, os.path.join(HERE, "sharded_bench.py"),
               "--quick", "--gate-json", gate]
        r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                           timeout=900)
        if r.returncode != 0:
            raise SystemExit(
                "sharded_bench subprocess failed "
                f"(rc={r.returncode}):\n{r.stdout}\n{r.stderr}")
        with open(gate) as f:
            return json.load(f)


def invariants(measured: dict) -> list[str]:
    """Machine-independent acceptance checks (no baseline involved):
    IVF must dominate exact search — faster *and* recall@10 ≥ the floor —
    on every gated backend."""
    failures = []
    for bname in GATE_BACKENDS:
        rec = measured[f"ivf_recall_at_10_{bname}"]
        if rec < RECALL_FLOOR:
            failures.append(
                f"ivf_recall_at_10_{bname}: {rec:.3f} < floor "
                f"{RECALL_FLOOR} (absolute)")
        iq, eq = measured[f"ivf_qps_{bname}"], measured[f"exact_qps_{bname}"]
        if iq <= eq:
            failures.append(
                f"ivf_qps_{bname}: {iq:.1f} <= exact_qps_{bname} {eq:.1f} "
                "(IVF must beat brute force)")
    ratio = measured["service_exact_ratio"]
    if ratio < SERVICE_RATIO_FLOOR:
        failures.append(
            f"service_exact_ratio: {ratio:.2f} < floor "
            f"{SERVICE_RATIO_FLOOR} (the front door may not cost more "
            "than this much of the bare engine's throughput)")
    if measured["service_lost_requests"]:
        failures.append(
            f"service_lost_requests: "
            f"{measured['service_lost_requests']:.0f} != 0 (every "
            "admitted request must resolve)")
    if measured["service_cache_identical"] != 1.0:
        failures.append(
            "service_cache_identical: cached result differed from the "
            "dispatch it replaced (must be bit-identical)")
    tiered = measured["tiered_cold_ratio"]
    if tiered < TIERED_RATIO_FLOOR:
        failures.append(
            f"tiered_cold_ratio: {tiered:.2f} < floor "
            f"{TIERED_RATIO_FLOOR} (a 5% hot-tier budget may not cost "
            "more than this much of fully-resident throughput)")
    if measured["sharded_parity"] != 1.0:
        failures.append(
            f"sharded_parity: {measured['sharded_parity']:.3f} != 1.0 "
            "(sharded serving must match single-host in ids AND score "
            "bytes on every backend, including mid-traffic "
            "update/compact)")
    if measured["sharded_lost_requests"]:
        failures.append(
            f"sharded_lost_requests: "
            f"{measured['sharded_lost_requests']:.0f} != 0 (every "
            "request admitted against a sharded version must resolve)")
    return failures


def compare(measured: dict, baseline: dict, tolerance: float,
            recall_tolerance: float) -> list[str]:
    failures = []
    base = baseline["metrics"]
    for name, direction in METRICS.items():
        if name not in base:
            continue
        have, want = measured[name], base[name]
        if direction == "higher" and have < want * (1.0 - tolerance):
            failures.append(f"{name}: {have:.1f} < floor "
                            f"{want * (1.0 - tolerance):.1f} "
                            f"(baseline {want:.1f}, -{tolerance:.0%})")
        elif direction == "lower" and have > want * (1.0 + tolerance):
            failures.append(f"{name}: {have:.2f} > ceiling "
                            f"{want * (1.0 + tolerance):.2f} "
                            f"(baseline {want:.2f}, +{tolerance:.0%})")
        elif direction == "recall" and have < want - recall_tolerance:
            failures.append(f"{name}: {have:.3f} < "
                            f"{want - recall_tolerance:.3f} "
                            f"(baseline {want:.3f}, "
                            f"-{recall_tolerance} abs)")
    return failures


def with_slack(metrics: dict, slack: float) -> dict:
    """Relax a measurement into a committable baseline (CI runners are
    slower and noisier than dev machines)."""
    out = {}
    for name, direction in METRICS.items():
        v = metrics[name]
        if direction == "higher":
            out[name] = round(v * (1.0 - slack), 2)
        elif direction == "lower":
            out[name] = round(v * (1.0 + slack), 3)
        else:
            out[name] = round(v - slack / 4.0, 3)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--quick", action="store_true",
                    help="accepted for lane uniformity (the gate is "
                    "always the quick configuration)")
    ap.add_argument("--n-docs", type=int, default=100_000)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--nlist", type=int, default=512)
    ap.add_argument("--nprobe", type=int, default=80)
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument("--output", default=None,
                    help="result JSON path (default BENCH_<git-sha>.json)")
    ap.add_argument("--tolerance", type=float, default=0.20,
                    help="max relative qps/latency regression (default "
                    "0.20 = fail on >20%%)")
    ap.add_argument("--recall-tolerance", type=float, default=0.05,
                    help="max absolute recall@k drop")
    ap.add_argument("--write-baseline", action="store_true",
                    help="record this measurement (with --slack folded "
                    "in) as the committed baseline and exit")
    ap.add_argument("--slack", type=float, default=0.5,
                    help="headroom folded into --write-baseline")
    ap.add_argument("--no-compare", action="store_true",
                    help="measure + write JSON, skip the gate")
    args = ap.parse_args(argv)

    sha = git_sha()
    print(f"ci_gate: measuring quick benches at {sha} "
          f"({args.n_docs} docs, {args.requests} requests x {args.batch}, "
          f"best of {args.repeats}) ...")
    metrics = measure(args.n_docs, args.requests, args.batch, args.k,
                      args.repeats, args.nlist, args.nprobe)
    for name in METRICS:
        print(f"  {name:24s} {metrics[name]:10.2f}")

    hard_failures = invariants(metrics)
    if hard_failures:
        print("\nACCEPTANCE INVARIANT VIOLATED:", file=sys.stderr)
        for line in hard_failures:
            print(f"  FAIL {line}", file=sys.stderr)
        return 1

    if args.write_baseline:
        doc = {"sha": sha, "config": {"n_docs": args.n_docs,
                                      "requests": args.requests,
                                      "batch": args.batch, "k": args.k,
                                      "nlist": args.nlist,
                                      "nprobe": args.nprobe},
               "slack": args.slack,
               "metrics": with_slack(metrics, args.slack)}
        with open(args.baseline, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"baseline written to {args.baseline} "
              f"(slack {args.slack:.0%})")
        return 0

    out_path = args.output or f"BENCH_{sha}.json"
    with open(out_path, "w") as f:
        json.dump({"sha": sha, "metrics": metrics}, f, indent=2,
                  sort_keys=True)
        f.write("\n")
    print(f"wrote {out_path}")

    if args.no_compare:
        return 0
    if not os.path.exists(args.baseline):
        print(f"ERROR: no baseline at {args.baseline} — run "
              "--write-baseline once and commit it", file=sys.stderr)
        return 2
    with open(args.baseline) as f:
        baseline = json.load(f)
    failures = compare(metrics, baseline, args.tolerance,
                       args.recall_tolerance)
    if failures:
        print(f"\nPERF REGRESSION vs baseline "
              f"(recorded at {baseline.get('sha', '?')}):",
              file=sys.stderr)
        for line in failures:
            print(f"  FAIL {line}", file=sys.stderr)
        return 1
    print(f"gate passed vs baseline {baseline.get('sha', '?')} "
          f"(tolerance {args.tolerance:.0%})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
