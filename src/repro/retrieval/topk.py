"""Exact maximum-similarity search with streaming (chunked) top-k.

Scoring never materialises the full (Q, D) matrix: the document axis is
scanned in chunks, keeping a running top-k per query (two-stage top-k — the
same schedule the Pallas kernels use on TPU, here expressed in jnp for the
host/reference path).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def resolve_k(k: int, n_docs: int) -> int:
    """The one ``k`` contract for every index class.

    ``k`` must be ≥ 1; a ``k`` beyond the corpus clamps to ``n_docs`` (the
    result then simply has fewer columns).  All five index classes
    (:class:`~repro.retrieval.index.DenseIndex`,
    :class:`~repro.retrieval.index.CompressedIndex`,
    :class:`~repro.retrieval.ivf.IVFIndex`, and both sharded wrappers) route
    through this guard so the clamping behaviour cannot drift.
    """
    if k < 1:
        raise ValueError(f"k must be ≥ 1, got {k}")
    return min(int(k), int(n_docs))


def resolve_nprobe(nprobe, nlist: int, default=None) -> int:
    """The one ``nprobe`` contract, mirroring :func:`resolve_k`.

    ``None`` falls back to ``default``; the result must be ≥ 1 and clamps
    to ``nlist`` (probing every list is simply exact search over the
    clustered corpus).  :class:`~repro.retrieval.ivf.IVFIndex`, the sharded
    IVF wrapper, and :class:`~repro.retrieval.segments.SegmentedIndex` all
    route through this guard so the clamping behaviour cannot drift.
    """
    if nprobe is None:
        nprobe = default
    if nprobe is None or nprobe < 1:
        raise ValueError(f"nprobe must be ≥ 1, got {nprobe}")
    return min(int(nprobe), int(nlist))


def topk_score_then_id(s: jax.Array, ids: jax.Array, k: int
                       ) -> tuple[jax.Array, jax.Array]:
    """Top-k by (score desc, doc id asc) — a strict total order.

    Exact search breaks score ties by document id implicitly (candidates
    are scanned in id order and ``lax.top_k`` keeps the first occurrence);
    IVF candidates arrive in probe order, sharded IVF candidates in shard
    order, and segmented candidates in layer order
    (:mod:`repro.retrieval.segments`), so ties must be broken *explicitly*
    on the id for all the paths to produce identical rankings.  Matters
    most for the 1-bit backend, whose integer sign-dot scores tie
    constantly.
    """
    order = jnp.lexsort((ids, -s), axis=-1)[..., :k]
    return (jnp.take_along_axis(s, order, axis=-1),
            jnp.take_along_axis(ids, order, axis=-1))


def masked_topk_by_id(s: jax.Array, ids: jax.Array, k: int
                      ) -> tuple[jax.Array, jax.Array]:
    """Top-``k`` by (score desc, id asc), normalising unreachable slots.

    ``-inf`` scores come back with id ``-1``; when fewer than ``k``
    candidate columns exist the output is padded out to ``k`` with
    ``(-inf, -1)``.  Shared by the single-host IVF search, both halves
    (shard-local and post-gather merge) of the sharded search, and the
    cross-layer merge of :class:`~repro.retrieval.segments.SegmentedIndex`,
    so the paths cannot drift apart.
    """
    kk = min(k, s.shape[1])
    vals, out = topk_score_then_id(s, ids, kk)
    out = jnp.where(jnp.isfinite(vals), out, -1)
    if kk < k:
        pad = k - kk
        vals = jnp.pad(vals, ((0, 0), (0, pad)), constant_values=-jnp.inf)
        out = jnp.pad(out, ((0, 0), (0, pad)), constant_values=-1)
    return vals, out


def merge_topk_block(run_v: jax.Array, run_i: jax.Array, cand_v: jax.Array,
                     cand_i: jax.Array, k: int
                     ) -> tuple[jax.Array, jax.Array]:
    """Merge a scored block into a (Q, k) running top-k — no sort.

    Same (score desc, id asc) strict total order as
    :func:`masked_topk_by_id`, computed as ``k`` rounds of max score →
    min doc id among the hits → retire the winner, instead of a variadic
    lexsort (XLA lowers that sort to a scalar comparator loop on CPU —
    ~1000× the cost of these k vectorised passes, and it has no TPU
    lowering at all; this formulation is what the fused Pallas kernel
    runs in VMEM, once per (list chunk, query block) tile).  Unreachable
    output slots are (−inf, −1), matching ``masked_topk_by_id``'s
    normalisation, whatever id a −inf candidate carried.

    Requires distinct (score, id) pairs among *reachable* candidates
    (−inf entries are exempt): a round retires every entry matching the
    winning pair at once.  IVF candidate streams satisfy this — each doc
    id appears in exactly one list chunk, merged once, and the running
    buffer holds previously-merged distinct ids.
    """
    cv = jnp.concatenate([run_v, cand_v], axis=1)
    ci = jnp.concatenate([run_i, cand_i], axis=1)
    kw = run_v.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, kw), 1)
    new_v = jnp.full((1, kw), float("-inf"), jnp.float32)
    new_i = jnp.full((1, kw), -1, jnp.int32)
    int_max = 2**31 - 1
    for t in range(k):
        m = jnp.max(cv, axis=1)                              # (Q,)
        hit = cv == m[:, None]
        sel = jnp.min(jnp.where(hit, ci, int_max), axis=1)   # min id among max
        new_v = jnp.where(col == t, m[:, None], new_v)
        new_i = jnp.where(col == t, sel[:, None], new_i)
        cv = jnp.where(hit & (ci == sel[:, None]), float("-inf"), cv)
    # unreachable rounds picked a (−inf, ·) entry: normalise the id to −1
    new_i = jnp.where(new_v == float("-inf"), -1, new_i)
    return new_v, new_i


def streaming_masked_topk(s: jax.Array, ids: jax.Array, k: int,
                          block: int) -> tuple[jax.Array, jax.Array]:
    """Blockwise-streamed :func:`masked_topk_by_id`.

    Scans the candidate axis in ``block``-wide slices, keeping a running
    (k,) partial top-k per query and merging each new block into it.
    Because (score desc, id asc) is a *strict total order*, the blockwise
    merge is associative and exact: the result is bit-identical to the
    monolithic ``masked_topk_by_id(s, ids, k)`` for **any** block size
    (property-tested in tests/test_ivf_fused.py).  The fused Pallas IVF
    kernel streams its list chunks through the same running top-k.
    """
    n = s.shape[1]
    if block < 1:
        raise ValueError(f"block must be ≥ 1, got {block}")
    run_v, run_i = masked_topk_by_id(s[:, :block], ids[:, :block], k)
    for ds in range(block, n, block):
        cv = jnp.concatenate([run_v, s[:, ds: ds + block]], axis=1)
        ci = jnp.concatenate([run_i, ids[:, ds: ds + block]], axis=1)
        run_v, run_i = masked_topk_by_id(cv, ci, k)
    return run_v, run_i


def similarity(queries: jax.Array, docs: jax.Array, sim: str) -> jax.Array:
    """(Q, d) × (D, d) → (Q, D) similarity. sim ∈ {"ip", "l2", "cos"}.

    "l2" returns the *negative squared* L2 distance so that maximum-similarity
    search is uniform across metrics (argmax).
    """
    if sim == "ip":
        return queries @ docs.T
    if sim == "cos":
        qn = queries / (jnp.linalg.norm(queries, axis=-1, keepdims=True) + 1e-12)
        dn = docs / (jnp.linalg.norm(docs, axis=-1, keepdims=True) + 1e-12)
        return qn @ dn.T
    if sim == "l2":
        q2 = jnp.sum(queries * queries, axis=-1, keepdims=True)
        d2 = jnp.sum(docs * docs, axis=-1)
        return -(q2 + d2[None, :] - 2.0 * (queries @ docs.T))
    raise ValueError(f"unknown similarity {sim!r}")


@functools.partial(jax.jit, static_argnames=("k", "sim"))
def _topk_chunk(queries, docs, base, k, sim):
    scores = similarity(queries, docs, sim)
    kk = min(k, docs.shape[0])
    vals, idx = jax.lax.top_k(scores, kk)
    return vals, idx + base


@functools.partial(jax.jit, static_argnames=("k",))
def merge_topk(vals_a, idx_a, vals_b, idx_b, k):
    """Merge two top-k candidate sets into one global top-k."""
    vals = jnp.concatenate([vals_a, vals_b], axis=-1)
    idx = jnp.concatenate([idx_a, idx_b], axis=-1)
    top_vals, pos = jax.lax.top_k(vals, k)
    return top_vals, jnp.take_along_axis(idx, pos, axis=-1)


def topk_search(queries: jax.Array, docs: jax.Array, k: int,
                sim: str = "ip", doc_chunk: int = 131072,
                query_chunk: int = 4096) -> tuple[jax.Array, jax.Array]:
    """Exact top-k over the document axis, streamed in chunks.

    Returns (scores (Q, k), indices (Q, k)), sorted by descending score.
    """
    n_docs = docs.shape[0]
    k = resolve_k(k, n_docs)

    out_vals, out_idx = [], []
    for qs in range(0, queries.shape[0], query_chunk):
        q = queries[qs: qs + query_chunk]
        vals = jnp.full((q.shape[0], k), -jnp.inf, jnp.float32)
        idx = jnp.zeros((q.shape[0], k), jnp.int32)
        for ds in range(0, n_docs, doc_chunk):
            d = docs[ds: ds + doc_chunk]
            cv, ci = _topk_chunk(q, d, ds, k, sim)
            if cv.shape[-1] < k:  # chunk smaller than k: pad
                pad = k - cv.shape[-1]
                cv = jnp.pad(cv, ((0, 0), (0, pad)),
                             constant_values=-jnp.inf)
                ci = jnp.pad(ci, ((0, 0), (0, pad)))
            vals, idx = merge_topk(vals, idx, cv, ci, k)
        out_vals.append(vals)
        out_idx.append(idx)
    return (jnp.concatenate(out_vals, axis=0),
            jnp.concatenate(out_idx, axis=0))
