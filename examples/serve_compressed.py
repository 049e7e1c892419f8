"""End-to-end serving driver: a request stream against the
:class:`~repro.serve.RetrievalService` front door, including a mid-stream
staged → canaried → promoted KB refresh.

    PYTHONPATH=src python examples/serve_compressed.py --requests 50
    PYTHONPATH=src python examples/serve_compressed.py --method pca_onebit

The index is described declaratively (:class:`IndexSpec`), built once with
:func:`build_index`, saved to a single ``.npz`` artifact, and the service
registers that artifact as version 1 of a named index — exactly like a
production serve process: no raw corpus, no re-fit.  Producer code then
streams query blocks through the async API (``service.query(...) →
QueryHandle``) while a background drain loop micro-batches and dispatches
them.  Halfway through, a *refreshed* corpus (the nightly-rebuild
scenario: new documents appended) is built into a second artifact, staged
off the serving path, canaried against live traffic via shadow overlap,
and promoted with zero downtime — requests keep flowing throughout and
each one ranks entirely against the version it bound to.

With ``--ivf-nlist`` the driver adds a third act: the refreshed KB is
streamed to a *chunked* (v3) artifact and hot-swapped in with only a 25%
hot-tier byte budget resident — the encoded inverted lists stay on disk,
Zipf-skewed open-loop traffic (the PR-7 load generator) keeps the LRU hot
tier warm, and the per-version ``stats()`` row reports the tier hit rate.

With ``--shards N`` a fourth act shards the refreshed KB over N devices
(``ShardSpec(shards=N)`` — run under
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to force host
devices) and hot-swaps it in: staging places every shard or none, the
promote is the same atomic pointer flip, results match the single-host
version bit-for-bit, and the ``stats()`` row grows a per-shard rollup.
"""

import argparse
import os
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.data import make_dpr_like_kb
from repro.retrieval import (IndexSpec, ShardSpec, build_index,
                             load_index_meta, save_index)
from repro.serve import QueryOptions, RetrievalService
from repro.utils import configure_compilation_cache, human_bytes


def serve_tiered(service, idx, tmp, queries):
    """Act three: same KB, v3 chunked artifact, 25% resident budget."""
    # the open-loop Zipf/Poisson generator lives in benchmarks/
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks.loadgen import (DEFAULT_MENU, build_workload,
                                    run_trial, warmup)

    path = os.path.join(tmp, "kb_v3")
    save_index(idx, path, chunked=True)
    enc = load_index_meta(path)["encoded_nbytes"]
    budget = max(1, enc // 4)
    print(f"\ntiered swap: v3 chunked artifact ({human_bytes(enc)} encoded "
          f"lists) staged at a 25% resident budget "
          f"({human_bytes(budget)})")
    service.stage("kb", artifact=path, resident_budget=budget)
    live = service.promote("kb")
    warmup(service, "kb", queries, DEFAULT_MENU, 64, 120.0)
    wl = build_workload(np.random.default_rng(3), duration_s=1.0,
                        rows_per_s=150.0, arrival="poisson",
                        menu=DEFAULT_MENU, pool_size=len(queries),
                        zipf_alpha=1.1)
    r = run_trial(service, "kb", queries, DEFAULT_MENU, wl)
    tier = service.stats()["indexes"]["kb"]["versions"][live]["tier"]
    print(f"  served {r['admitted']} open-loop requests "
          f"(p50={r['p50_ms']:.1f}ms p99={r['p99_ms']:.1f}ms, "
          f"{r['lost']} lost)")
    print(f"  tier: hit rate {tier['hit_rate']:.1%} "
          f"({tier['hits']} hits, {tier['misses']} misses), "
          f"{human_bytes(tier['bytes_resident'])} of "
          f"{human_bytes(tier['budget_bytes'])} hot tier resident, "
          f"{human_bytes(tier['bytes_read'])} paged from disk")


def serve_sharded(service, spec, docs, sample, queries, n_shards, batch, k):
    """Act four: the same KB sharded over the device mesh, hot-swapped in
    behind the same front door — identical results, per-shard rollup."""
    n_dev = jax.device_count()
    if n_shards > n_dev:
        print(f"\nsharded swap skipped: --shards {n_shards} wants more "
              f"devices than available ({n_dev}) — run under "
              f"XLA_FLAGS=--xla_force_host_platform_device_count="
              f"{n_shards}")
        return
    print(f"\nsharded swap: rebuilding over ShardSpec(shards={n_shards}) "
          f"({n_dev} devices attached)")
    import dataclasses
    sharded = build_index(
        dataclasses.replace(spec, shard=ShardSpec(shards=n_shards)),
        docs, sample)
    before = service.query(queries[:batch],
                           QueryOptions(index="kb", k=k)).result(timeout=120)
    service.stage("kb", index=sharded)   # places every shard, or raises
    live = service.promote("kb")
    after = service.query(queries[:batch],
                          QueryOptions(index="kb", k=k)).result(timeout=120)
    same_ids = np.array_equal(before.ids, after.ids)
    same_bits = same_ids and before.scores.tobytes() == after.scores.tobytes()
    row = service.stats()["indexes"]["kb"]["versions"][live]
    # quantizer-tail pipelines (--no-post) are bit-identical in score
    # bytes too; post-CenterNorm specs score on the float decode path,
    # where ids still match but the last ulp may differ per shard shape
    verdict = "bit-identical" if same_bits else \
        "same top-k ids" if same_ids else "DIVERGED"
    print(f"  promoted v{live}: results vs single-host {verdict}")
    for s in row.get("shards", []):
        lists = f", {s['n_lists']} lists" if "n_lists" in s else ""
        print(f"    shard {s['shard']}: {s['n_docs']} docs{lists}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--method", default="pca_int8",
                    choices=("pca_int8", "pca_onebit", "onebit", "int8"))
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--n-docs", type=int, default=50_000)
    ap.add_argument("--requests", type=int, default=20)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--no-post", action="store_true",
                    help="skip post-quantization CenterNorm: storage stays "
                         "quantized and scoring runs the int8/1-bit kernels")
    ap.add_argument("--ivf-nlist", type=int, default=0,
                    help="build an IVF index with this many lists "
                         "(0 = exact search)")
    ap.add_argument("--ivf-nprobe", type=int, default=0,
                    help="default probe width (0 = nlist/2); every 4th "
                         "request overrides it per-request to nlist")
    ap.add_argument("--shards", type=int, default=0,
                    help="fourth act: hot-swap in the KB sharded over "
                         "this many devices (needs XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    args = ap.parse_args(argv)
    configure_compilation_cache()
    dev = jax.devices()[0]
    device = f"{dev.platform} {dev.device_kind}"

    dim = 245 if args.method == "pca_onebit" else args.dim
    kb = make_dpr_like_kb(n_queries=max(64, args.requests * args.batch),
                          n_docs=args.n_docs)
    fresh = make_dpr_like_kb(n_queries=8, n_docs=max(64, args.n_docs // 20),
                             seed=1)

    ivf = None
    full_probe = None
    if args.ivf_nlist:
        nprobe = args.ivf_nprobe or max(1, args.ivf_nlist // 2)
        ivf = (args.ivf_nlist, nprobe)
        full_probe = args.ivf_nlist

    spec = IndexSpec(method=args.method, dim=dim, post=not args.no_post,
                     ivf=ivf)
    queries = np.asarray(kb.queries)
    k = args.k

    def build_artifact(docs, path, tag):
        idx = build_index(spec, docs, kb.queries[:512])
        idx.save(path)
        print(f"  {tag}: {len(idx)} docs, scorer {idx.scorer.name}, "
              f"artifact {human_bytes(os.path.getsize(path))}")
        return idx

    served = [0]

    def stream(service, lo, hi):
        """Submit requests [lo, hi); resolve async handles as they land."""
        handles = []
        for r in range(lo, hi):
            nprobe = full_probe if (full_probe and r % 4 == 3) else None
            off = (r * args.batch) % max(1, len(queries) - args.batch)
            handles.append(service.query(
                queries[off: off + args.batch],
                QueryOptions(index="kb", k=k, nprobe=nprobe)))
        for h in handles:
            h.result(timeout=120)
        served[0] += len(handles)

    print(f"building v1 index from spec [{args.method}"
          f"{', ivf=' + str(ivf) if ivf else ''}] ...")
    with tempfile.TemporaryDirectory() as tmp:
        path_v1 = os.path.join(tmp, "kb_v1.npz")
        path_v2 = os.path.join(tmp, "kb_v2.npz")
        build_artifact(kb.docs, path_v1, "v1")

        with RetrievalService(default_k=k,
                              max_batch=args.max_batch) as service:
            service.register("kb", artifact=path_v1)
            print("  service cold-started from the artifact "
                  "(no corpus, no re-fit)\n")

            half, three_q = args.requests // 2, (3 * args.requests) // 4
            stream(service, 0, half)

            # the nightly refresh: corpus grows, new artifact is staged off
            # the serving path, canaried on live traffic, then promoted
            print(f"refresh after {served[0]} requests: building v2 "
                  f"(+{len(fresh.docs)} new docs) while serving continues")
            docs_v2 = jnp.concatenate([kb.docs, fresh.docs], axis=0)
            idx_v2 = build_artifact(docs_v2, path_v2, "v2")
            service.stage("kb", artifact=path_v2, canary_every=2)
            stream(service, half, max(half + 1, three_q))
            canary = service.canary("kb")
            print(f"  canary: overlap {canary['overlap']:.3f} over "
                  f"{canary['batches']} sampled batches")
            live = service.promote("kb", min_overlap=0.5)
            print(f"  promoted v{live} (rollback(\"kb\") would undo)\n")
            stream(service, max(half + 1, three_q), args.requests)

            stats = service.stats()
            table = stats["indexes"]["kb"]
            print(f"served {stats['requests_served']} requests "
                  f"({stats['queries_served']} queries, "
                  f"{stats['batches_served']} micro-batches) across "
                  f"versions {sorted(table['versions'])}, "
                  f"live=v{table['live']}")
            print(f"  latency p50={stats['p50_ms']:.1f}ms "
                  f"p95={stats['p95_ms']:.1f}ms "
                  f"p99={stats['p99_ms']:.1f}ms  ({device})")
            print(f"  admission: {stats['pending_queries']} pending, "
                  f"{stats['requests_rejected']} rejected")

            if ivf:
                serve_tiered(service, idx_v2, tmp, queries)

            if args.shards:
                serve_sharded(service, spec, docs_v2, kb.queries[:512],
                              queries, args.shards, args.batch, k)


if __name__ == "__main__":
    sys.exit(main())
