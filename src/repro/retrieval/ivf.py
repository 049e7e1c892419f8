"""IVF approximate nearest-neighbour search over quantized storage.

Reproduces the paper's Figure-1 retrieval condition (FAISS ``IndexIVFFlat``,
nlist=200, nprobe=100) and extends it to the compressed-serving path: a
k-means coarse quantizer partitions the index into ``nlist`` inverted lists;
search scores only the ``nprobe`` lists nearest to each query.

Unlike the seed implementation (full float32 docs, bespoke einsum scoring),
:class:`IVFIndex` stores the inverted lists in *scorer-backend storage*
(float / fp16 / int8 codes / bit-packed 1-bit words, via the
:mod:`repro.retrieval.scorers` registry) and scores probed candidates through
the same kernel paths as exact search — so ANN search compounds with the
paper's compression instead of forfeiting it.  The whole query path is one
jit graph per (k, nprobe): float stages → coarse routing → list gather →
``scorer.scores_gathered`` → masked top-k.

Implementation notes (TPU/JAX adaptation): inverted lists are stored as one
padded (nlist, max_len) id matrix so probing is a dense gather; masked
scoring keeps everything jit-compatible.  For the production multi-pod path
the lists are partitioned over devices (:class:`repro.retrieval.sharded.
ShardedIVFIndex`) — IVF then reduces per-device compute by nprobe/nlist
while the collective schedule is unchanged.

Degenerate corpora are handled explicitly: ``fit`` clamps the effective
``nlist`` to the number of documents (a k-means run can still leave a
cluster empty — those lists are simply padded), and ``search`` always
returns ``min(k, n_docs)`` columns, padding truly-unreachable slots (fewer
than k candidates probed) with score ``-inf`` and id ``-1``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pipeline import CompressionPipeline
from repro.retrieval.kmeans import assign, assign_balanced, kmeans_fit
from repro.retrieval.scorers import (Scorer, apply_float_stages,
                                     scorer_for_pipeline)
from repro.retrieval.topk import (masked_topk_by_id, merge_topk_block,
                                  resolve_k, resolve_nprobe, similarity,
                                  topk_score_then_id)

__all__ = ["IVFIndex", "IVFFlatIndex", "build_padded_lists",
           "probe_and_score", "masked_topk_by_id", "topk_score_then_id"]


#: probe slots gathered + scored per streaming step.  Merging is
#: associative under the strict (score desc, id asc) order, so any
#: grouping returns identical results — the block size only trades peak
#: memory (``g·max_len`` candidate rows) against per-step dispatch
#: overhead.  Measured on the CPU jnp path (100k docs, nlist=512,
#: nprobe=64, int8): 2 beats 1 by ~10% and beats 4–16 by 1.4–2.3× —
#: wider blocks thrash cache on the gather and widen every merge.
PROBE_BLOCK = 2


def _pad_probe(probe: jax.Array, lists: jax.Array, extras: list[jax.Array],
               g: int):
    """Pad the probe table to a multiple of ``g`` slots with a phantom
    all-pad list (id ``nlist``), so grouped streaming never double-counts
    a real list.  ``extras`` are per-(query, probe) columns (e.g. routed
    centroid scores) padded alongside; their pad value is irrelevant —
    every phantom candidate is masked by id −1."""
    nlist = lists.shape[0]
    lists_ext = jnp.concatenate(
        [lists, jnp.full((1, lists.shape[1]), -1, lists.dtype)])
    npad = -(-probe.shape[1] // g) * g
    if npad != probe.shape[1]:
        fill = npad - probe.shape[1]
        probe = jnp.concatenate(
            [probe, jnp.full((probe.shape[0], fill), nlist, probe.dtype)],
            axis=1)
        extras = [jnp.concatenate(
            [e, jnp.zeros((e.shape[0], fill), e.dtype)], axis=1)
            for e in extras]
    return probe, lists_ext, extras


def probe_and_score(q: jax.Array, centroids: jax.Array, lists: jax.Array,
                    storage: jax.Array, scorer: Scorer, params, sim: str,
                    nprobe: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Coarse-route ``q`` to ``nprobe`` lists, gather and score candidates.

    Returns ``(scores, cand, valid)``: scores ``(Q, C)`` with pad slots at
    ``-inf``, the gathered candidate row ids ``(Q, C)`` (−1 pads), and the
    validity mask.  The caller maps ``cand`` to output ids (global ids on
    the single host, shard-local → global via a gids table when sharded).

    The probed lists are gathered and scored ``PROBE_BLOCK`` slots at a
    time inside a ``lax.scan``, so the peak intermediate is one
    ``(Q, g·max_len, w)`` block — never the full
    ``(Q, nprobe·max_len, w)`` gather the old implementation
    materialised.  Output column order is unchanged (probe-major), so
    results are identical.
    """
    cscores = similarity(q, centroids, sim)
    _, probe = jax.lax.top_k(cscores, nprobe)          # (Q, nprobe)
    qe = scorer.encode_queries(q)
    g = min(PROBE_BLOCK, nprobe)
    probe, lists_ext, _ = _pad_probe(probe, lists, [], g)
    n_q = q.shape[0]
    steps = jnp.moveaxis(probe.reshape(n_q, -1, g), 1, 0)   # (S, Q, g)

    def step(_, pj):                                   # pj: (Q, g) slots
        cand_j = lists_ext[pj].reshape(n_q, -1)        # (Q, g·L)
        gathered = storage[jnp.maximum(cand_j, 0)]     # (Q, g·L, w)
        s_j = scorer.scores_gathered(qe, gathered, params=params)
        return None, (s_j, cand_j)

    _, (s, cand) = jax.lax.scan(step, None, steps)     # (S, Q, g·L)
    width = nprobe * lists.shape[1]
    s = jnp.moveaxis(s, 0, 1).reshape(n_q, -1)[:, :width]
    cand = jnp.moveaxis(cand, 0, 1).reshape(n_q, -1)[:, :width]
    valid = cand >= 0
    return jnp.where(valid, s, -jnp.inf), cand, valid


def build_padded_lists(labels: np.ndarray, nlist: int) -> np.ndarray:
    """(n_docs,) cluster labels → (nlist, max_len) id matrix, −1 padded.

    Empty clusters become all-pad rows (the ``nlist > n_docs`` /
    empty-bucket case), never a crash.  One stable argsort buckets every
    doc — O(n log n + nlist), not a per-cluster scan — and keeps doc ids
    ascending within each list (the tie order the search paths rely on).
    """
    order = np.argsort(labels, kind="stable").astype(np.int32)
    counts = np.bincount(labels, minlength=nlist)
    max_len = max(1, int(counts.max(initial=0)))
    lists = np.full((nlist, max_len), -1, np.int32)
    starts = np.concatenate([[0], np.cumsum(counts)])
    for c in range(nlist):
        b = order[starts[c]: starts[c + 1]]
        lists[c, : len(b)] = b
    return lists


class IVFIndex:
    """Quantized IVF index: coarse k-means router over scorer-backend storage.

    ``pipeline`` follows :class:`~repro.retrieval.index.CompressedIndex`
    semantics: float stages transform docs/queries, a trailing quantizer (if
    any) selects the scorer backend that owns the stored representation.
    ``pipeline=None`` stores plain float (the classic IVF-Flat).

    ``fit`` clamps the effective ``nlist`` to the corpus size; ``nprobe``
    is clamped to ``nlist`` at search time and can be overridden per call
    (and per request through :class:`repro.serve.ServeEngine`).
    """

    def __init__(self, pipeline: Optional[CompressionPipeline] = None,
                 nlist: int = 200, nprobe: int = 100, sim: str = "ip",
                 backend: str = "auto", kmeans_iters: int = 15,
                 residual: bool = False, kmeans_init: str = "random",
                 balanced: bool = False):
        if nlist < 1:
            raise ValueError("nlist must be ≥ 1")
        if residual and sim != "ip":
            raise ValueError("residual encoding is IP-only: the routed "
                             "q·centroid correction is an inner-product "
                             f"identity (got sim={sim!r})")
        self.pipeline = pipeline if pipeline is not None \
            else CompressionPipeline([])
        self.nlist = nlist
        self._nlist_requested = nlist  # clamp is per-fit, never sticky
        self.nprobe = nprobe
        self.sim = sim
        self.backend = backend
        self.kmeans_iters = kmeans_iters
        self.residual = residual       # store encode(x − centroid[label])
        self.kmeans_init = kmeans_init  # "random" (historical) or "++"
        self.balanced = balanced       # capacity-aware list assignment
        self.float_stages, self.scorer = scorer_for_pipeline(
            self.pipeline, sim=sim, backend=backend)
        self.centroids: Optional[jax.Array] = None   # (nlist, d) float routing
        self.lists: Optional[jax.Array] = None       # (nlist, max_len), −1 pad
        self.storage: Optional[jax.Array] = None     # scorer-encoded rows
        self.spec = None               # set by api.build_index / api.load_index
        self._labels: Optional[np.ndarray] = None    # (n_docs,) cluster ids
        self._n_docs = 0
        self._dim = 0
        self._version = 0      # bumped on every fit/add; snapshots check it
        self._source = None    # (CompressedIndex, version) when promoted
        self._search_fn = None
        self._list_layout = None       # lazy list-major (version, stor, ids)
        # summed over fused-kernel launches: query rows × nprobe, what a
        # (row, probe) grid would fetch, and the list steps of the launched
        # grids, min(nlist, rows × nprobe) a launch, one fetch of each list
        self.probe_pairs = 0
        self.list_steps = 0
        self._fused_reference_only = False   # tests: force the jnp ref mirror
        self.store = None              # ListStore when tiered (storage=None)
        self._store_fns = None         # lazy (route_fn, step_fn) jit pair

    # -- construction -----------------------------------------------------
    @classmethod
    def build(cls, docs: jax.Array,
              queries_sample: Optional[jax.Array] = None,
              pipeline: Optional[CompressionPipeline] = None, *,
              nlist: int = 200, nprobe: int = 100, sim: str = "ip",
              backend: str = "auto", kmeans_iters: int = 15,
              residual: bool = False, kmeans_init: str = "random",
              balanced: bool = False, rng=None) -> "IVFIndex":
        """Fit the pipeline on ``docs`` then fit the IVF structure."""
        pipeline = pipeline if pipeline is not None else CompressionPipeline([])
        pipeline.fit(docs, queries_sample, rng=rng)
        idx = cls(pipeline, nlist=nlist, nprobe=nprobe, sim=sim,
                  backend=backend, kmeans_iters=kmeans_iters,
                  residual=residual, kmeans_init=kmeans_init,
                  balanced=balanced)
        return idx.fit(docs, rng=rng)

    def fit(self, docs: jax.Array, rng=None,
            train_size: int = 100_000) -> "IVFIndex":
        """Encode ``docs`` through the (already fitted) pipeline and build
        the coarse router + inverted lists."""
        x = apply_float_stages(self.float_stages, docs, "docs")
        if self.residual:
            # route first, then encode what the router cannot explain:
            # storage = encode(x − centroid[label]).  At IP scoring time the
            # routed q·centroid term is added back, so for float storage the
            # identity q·(x−c) + q·c = q·x makes residual encoding *exact*;
            # for quantized storage the encoder only has to cover the
            # (much smaller) residual range, cutting quantization error.
            x = jnp.asarray(x, jnp.float32)
            if x.shape[0] == 0:
                raise ValueError("cannot fit an IVF index on an empty corpus")
            self._fit_router(x, rng=rng, train_size=train_size)
            res = x - self.centroids[jnp.asarray(self._labels)]
            return self._finish_install(self.scorer.encode_docs(res), x)
        storage = self.scorer.encode_docs(x)
        return self._install(storage, x, rng=rng, train_size=train_size)

    def _fit_router(self, x_route: jax.Array, rng=None,
                    train_size: int = 100_000) -> None:
        """k-means centroids + list assignment from float routing vectors."""
        n_docs = int(x_route.shape[0])
        if rng is None:
            rng = jax.random.PRNGKey(0)
        # clamp to this corpus, from the *requested* nlist — a refit on a
        # larger corpus gets the configured list count back
        self.nlist = max(1, min(self._nlist_requested, n_docs))
        train = x_route
        if n_docs > train_size:
            sel = jax.random.choice(rng, n_docs, (train_size,), replace=False)
            train = x_route[sel]
        self.centroids = kmeans_fit(train, self.nlist, self.kmeans_iters,
                                    rng, init=self.kmeans_init)
        if self.balanced and n_docs > self.nlist:
            labels = assign_balanced(x_route, self.centroids)
        else:
            labels = assign(x_route, self.centroids)
        self._labels = np.asarray(labels)
        self.lists = jnp.asarray(build_padded_lists(self._labels, self.nlist))

    def _finish_install(self, storage: jax.Array, x_route: jax.Array
                        ) -> "IVFIndex":
        self.storage = storage
        self._n_docs = int(storage.shape[0])
        self._dim = int(x_route.shape[-1])
        self._version += 1
        self._source = None    # fresh fit: no longer a shared-storage view
        self._search_fn = None
        self._list_layout = None
        self.store = None      # a fresh fit is fully resident
        self._store_fns = None
        return self

    def _install(self, storage: jax.Array, x_route: jax.Array, rng=None,
                 train_size: int = 100_000) -> "IVFIndex":
        """Install pre-encoded ``storage`` with routing vectors ``x_route``
        (float, same row order) — shared by ``fit`` and
        :meth:`CompressedIndex.to_ivf <repro.retrieval.index.CompressedIndex.to_ivf>`."""
        if self.residual:
            raise ValueError("residual IVF cannot adopt pre-encoded storage "
                             "(rows must be re-encoded against the routed "
                             "centroids) — use fit()")
        n_docs = int(storage.shape[0])
        if n_docs == 0:
            raise ValueError("cannot fit an IVF index on an empty corpus")
        x_route = jnp.asarray(x_route, jnp.float32)
        self._fit_router(x_route, rng=rng, train_size=train_size)
        return self._finish_install(storage, x_route)

    def _install_routed(self, storage: jax.Array, labels: np.ndarray,
                        centroids: jax.Array, dim: int) -> "IVFIndex":
        """Adopt pre-encoded storage already routed to an *existing* router
        — no k-means refit, no float decode.  This is the chunked-compaction
        fold: a store-backed main cannot decode its whole corpus to refit,
        but its delta rows were routed to the same centroids, so keeping the
        router and rebuilding only the list table is exact."""
        if self.residual:
            raise ValueError("residual IVF cannot adopt pre-encoded storage")
        storage = jnp.asarray(storage)
        if storage.shape[0] == 0:
            raise ValueError("cannot install an empty corpus")
        self.centroids = jnp.asarray(centroids)
        self.nlist = int(self.centroids.shape[0])
        self._labels = np.asarray(labels)
        if self._labels.shape != (int(storage.shape[0]),):
            raise ValueError("labels must be one cluster id per storage row")
        self.lists = jnp.asarray(build_padded_lists(self._labels, self.nlist))
        return self._finish_install(storage, jnp.zeros((0, dim), jnp.float32))

    def add(self, docs: jax.Array) -> "IVFIndex":
        """Append docs, routing them to the *existing* centroids (no refit)."""
        if self.store is not None:
            raise ValueError(
                "store-backed (tiered) IVF index is read-only — wrap it in "
                "a SegmentedIndex for live updates, or reload with "
                "resident='all'")
        if self.centroids is None:
            return self.fit(docs)
        x = apply_float_stages(self.float_stages, docs, "docs")
        x_f = jnp.asarray(x, jnp.float32)
        labels = np.asarray(assign(x_f, self.centroids))
        if self.residual:
            enc = self.scorer.encode_docs(
                x_f - self.centroids[jnp.asarray(labels)])
        else:
            enc = self.scorer.encode_docs(x)
        self.storage = jnp.concatenate([self.storage, enc], axis=0)
        self._labels = np.concatenate([self._labels, labels])
        self.lists = jnp.asarray(build_padded_lists(self._labels, self.nlist))
        self._n_docs = int(self.storage.shape[0])
        self._version += 1
        self._source = None    # storage was copied on append: now our own
        self._search_fn = None
        self._list_layout = None
        self._store_fns = None
        return self

    def __len__(self) -> int:
        return self._n_docs

    @property
    def nbytes(self) -> int:
        """Bytes of the quantized document storage (the paper's metric).

        For a store-backed index this is the *encoded artifact* size — what
        a fully-resident load would cost — not the hot-tier residency
        (``store.stats()['bytes_resident']`` reports that)."""
        if self.storage is not None:
            return int(self.storage.size * self.storage.dtype.itemsize)
        assert self.store is not None
        return int(self.store.encoded_nbytes)

    @property
    def aux_nbytes(self) -> int:
        """Routing overhead: centroids + padded inverted lists (+ the
        list-major storage copy once the fused kernel path materialises it)."""
        aux = 0
        for a in (self.centroids, self.lists):
            if a is not None:
                aux += int(a.size * a.dtype.itemsize)
        if self._list_layout is not None:
            for a in self._list_layout[1:]:
                aux += int(a.size * a.dtype.itemsize)
        return aux

    # -- search ------------------------------------------------------------
    def encode_queries(self, queries: jax.Array) -> jax.Array:
        """Queries through the float stages (no query-side quantization)."""
        return apply_float_stages(self.float_stages, queries, "queries")

    def _resolve_nprobe(self, nprobe: Optional[int]) -> int:
        return resolve_nprobe(nprobe, self.nlist, default=self.nprobe)

    @property
    def _use_fused_kernel(self) -> bool:
        """Route search through the fused Pallas kernel?

        The kernel covers the IP hot path for all four storage formats; the
        1-bit backend additionally needs the paper's α = 0.5 offset (any
        other offset has rank-1 corrections the standalone op applies
        outside the kernel).  Everything else falls back to the streaming
        jnp path, which is the numerics oracle anyway.  A store-backed
        index always streams: the fused kernel DMAs a device-resident
        list-major copy of the whole storage, which is exactly what a
        tiered index does not have.
        """
        if self.store is not None:
            return False
        if not self.scorer.use_pallas or self.sim != "ip":
            return False
        if self.scorer.name == "onebit":
            return float(self.scorer.quantizer.offset) == 0.5
        return True

    def _list_major_layout(self) -> tuple[jax.Array, jax.Array]:
        """(nlist, max_len, w) list-major storage + (nlist, max_len) ids.

        The fused kernel DMAs inverted lists in chunks, so rows must be
        contiguous per list; ``max_len`` is padded here, once, to
        ``LIST_ALIGN`` (−1 ids), so no search relays the storage out.
        Built lazily on the first fused search and cached against
        ``_version`` (counted in :attr:`aux_nbytes`); the canonical
        row-major ``storage`` stays the single source of truth for
        persistence, sharding, and the jnp path.
        """
        if self._list_layout is not None and \
                self._list_layout[0] == self._version:
            return self._list_layout[1], self._list_layout[2]
        from repro.kernels.ivf_fused.kernel import LIST_ALIGN
        tail = -self.lists.shape[1] % LIST_ALIGN
        lists = jnp.pad(self.lists, ((0, 0), (0, tail)), constant_values=-1)
        list_storage = self.storage[jnp.maximum(lists, 0)]
        pad = (lists < 0)[..., None]
        if list_storage.ndim == 3:
            list_storage = jnp.where(pad, jnp.zeros((), list_storage.dtype),
                                     list_storage)
        self._list_layout = (self._version, list_storage, lists)
        return list_storage, lists

    def _streaming_search_fn(self):
        """jit'd route→scan(gather→score→merge) streaming top-k (jnp path).

        ``PROBE_BLOCK`` probed lists are gathered and scored per scan step
        through the backend's ``scores_gathered`` oracle, then folded into
        a (Q, k) running top-k with the shared (score desc, id asc) merge
        — exact and bit-identical to the old monolithic masked top-k (the
        order is total, so blockwise merging is associative for any block
        size), but the peak intermediate drops from (Q, nprobe·max_len)
        to (Q, g·max_len).
        """
        stages = tuple(self.float_stages)
        scorer = self.scorer
        sim = self.sim
        residual = self.residual

        @functools.partial(jax.jit, static_argnames=("k", "nprobe"))
        def _search(queries, centroids, lists, storage, params, *, k, nprobe):
            q = queries
            for t in stages:
                q = t(q, "queries")
            cscores = similarity(q, centroids, sim)
            cvals, probe = jax.lax.top_k(cscores, nprobe)   # (Q, nprobe)
            qe = scorer.encode_queries(q)
            n_q, max_len = q.shape[0], lists.shape[1]
            g = min(PROBE_BLOCK, nprobe)
            probe, lists_ext, (cvals,) = _pad_probe(probe, lists, [cvals], g)
            p_steps = jnp.moveaxis(probe.reshape(n_q, -1, g), 1, 0)
            c_steps = jnp.moveaxis(cvals.reshape(n_q, -1, g), 1, 0)

            def step(carry, inp):
                pj, cj = inp                               # (Q, g) slots
                cand_j = lists_ext[pj].reshape(n_q, -1)    # (Q, g·L)
                gathered = storage[jnp.maximum(cand_j, 0)]
                s_j = scorer.scores_gathered(qe, gathered, params=params)
                if residual:                   # routed q·centroid term
                    s_j = s_j + jnp.repeat(cj, max_len, axis=1)
                s_j = jnp.where(cand_j >= 0, s_j, -jnp.inf)
                rv, ri = carry
                # the sort-free merge (k max/min-id rounds): XLA's CPU
                # lowering of the lexsort merge is a scalar comparator
                # loop that dominated the whole search (~70% of the
                # hot path at nlist=512); bit-identical by the strict
                # total order, see topk.merge_topk_block
                return merge_topk_block(
                    rv, ri, s_j,
                    jnp.where(cand_j >= 0, cand_j, -1), k), None

            init = (jnp.full((n_q, k), -jnp.inf, jnp.float32),
                    jnp.full((n_q, k), -1, jnp.int32))
            (vals, ids), _ = jax.lax.scan(step, init, (p_steps, c_steps))
            return vals, ids

        return _search

    # -- tiered (store-backed) search --------------------------------------
    def _store_fn_pair(self):
        """jit'd (route, step) pair for the store-backed streaming search.

        The two graphs together are an exact mirror of
        :meth:`_streaming_search_fn`, split at the host boundary where list
        bytes come from the :class:`~repro.storage.store.ListStore` instead
        of a device gather.  Bit-identity holds unconditionally: the route
        graph runs the same ops (stages → similarity → top_k →
        encode_queries); each step scores the same ``(Q, g·max_len)`` block
        through the same ``scores_gathered`` oracle and folds it with the
        same associative merge.  Pad slots differ in *content* (zero rows
        here vs row-0 gathers there) but every pad score is masked to
        ``-inf`` before the merge, and a matmul output column depends only
        on its own input column — pad bytes can never reach a kept bit.
        """
        if self._store_fns is not None:
            return self._store_fns
        stages = tuple(self.float_stages)
        scorer = self.scorer
        sim = self.sim
        residual = self.residual

        @functools.partial(jax.jit, static_argnames=("nprobe",))
        def _route(queries, centroids, *, nprobe):
            q = queries
            for t in stages:
                q = t(q, "queries")
            cscores = similarity(q, centroids, sim)
            cvals, probe = jax.lax.top_k(cscores, nprobe)   # (Q, nprobe)
            return scorer.encode_queries(q), probe, cvals

        @functools.partial(jax.jit, static_argnames=("k", "max_len"))
        def _step(qe, gathered, cand_j, cj, rv, ri, params, *, k, max_len):
            s_j = scorer.scores_gathered(qe, gathered, params=params)
            if residual:                   # routed q·centroid term
                s_j = s_j + jnp.repeat(cj, max_len, axis=1)
            s_j = jnp.where(cand_j >= 0, s_j, -jnp.inf)
            return merge_topk_block(
                rv, ri, s_j, jnp.where(cand_j >= 0, cand_j, -1), k)

        self._store_fns = (_route, _step)
        return self._store_fns

    def _gather_block(self, pj: np.ndarray, g: int, max_len: int
                      ) -> tuple[np.ndarray, np.ndarray]:
        """Assemble one scoring block from the store: ``pj`` is the (Q, g)
        probe slice (phantom pad slots carry id ``nlist``); returns the
        zero-filled ``(Q, g·L, w)`` gathered rows and the −1-filled
        ``(Q, g·L)`` candidate ids.  Lists repeated across queries within
        the block are fetched once (one touch per block, so the store's
        frequency-aware admission counts probes, not fan-out)."""
        store = self.store
        n_q = pj.shape[0]
        gathered = np.zeros((n_q, g * max_len, store.storage_width),
                            store.storage_dtype)
        cand = np.full((n_q, g * max_len), -1, np.int32)
        block: dict[int, tuple[np.ndarray, np.ndarray]] = {}
        for qi in range(n_q):
            for j in range(g):
                lid = int(pj[qi, j])
                if lid >= self.nlist:          # phantom pad slot
                    continue
                entry = block.get(lid)
                if entry is None:
                    entry = block[lid] = store.get(lid)
                rows, ids = entry
                n = ids.shape[0]
                if n:
                    gathered[qi, j * max_len: j * max_len + n] = rows
                    cand[qi, j * max_len: j * max_len + n] = ids
        return gathered, cand

    def _store_search(self, queries: jax.Array, k: int, nprobe: int,
                      query_chunk: int) -> tuple[jax.Array, jax.Array]:
        """Streaming search with list bytes served by :attr:`store`."""
        route, step = self._store_fn_pair()
        params = self.scorer.params()
        max_len = max(1, int(self.store.max_len))
        g = min(PROBE_BLOCK, nprobe)
        npad = -(-nprobe // g) * g
        vals_out, idx_out = [], []
        for s in range(0, queries.shape[0], query_chunk):
            qc = queries[s: s + query_chunk]
            qe, probe, cvals = route(qc, self.centroids, nprobe=nprobe)
            probe_np = np.asarray(probe)
            cvals_np = np.asarray(cvals)
            n_q = probe_np.shape[0]
            if npad != nprobe:                 # mirror _pad_probe
                fill = npad - nprobe
                probe_np = np.concatenate(
                    [probe_np,
                     np.full((n_q, fill), self.nlist, probe_np.dtype)],
                    axis=1)
                cvals_np = np.concatenate(
                    [cvals_np, np.zeros((n_q, fill), cvals_np.dtype)],
                    axis=1)
            rv = jnp.full((n_q, k), -jnp.inf, jnp.float32)
            ri = jnp.full((n_q, k), -1, jnp.int32)
            for j0 in range(0, npad, g):
                gathered, cand = self._gather_block(
                    probe_np[:, j0: j0 + g], g, max_len)
                rv, ri = step(qe, jnp.asarray(gathered), jnp.asarray(cand),
                              jnp.asarray(cvals_np[:, j0: j0 + g]),
                              rv, ri, params, k=k, max_len=max_len)
            vals_out.append(rv)
            idx_out.append(ri)
        return jnp.concatenate(vals_out), jnp.concatenate(idx_out)

    def prefetch(self, queries: jax.Array,
                 nprobe: Optional[int] = None) -> int:
        """Warm the store's hot tier with the probe table for ``queries``
        (route only — no scoring); returns lists touched.  No-op (0) on a
        fully-resident index."""
        if self.store is None:
            return 0
        nprobe = self._resolve_nprobe(nprobe)
        route, _ = self._store_fn_pair()
        _, probe, _ = route(jnp.asarray(queries), self.centroids,
                            nprobe=nprobe)
        lids = np.unique(np.asarray(probe).ravel())
        return self.store.prefetch(lids[lids < self.nlist].tolist())

    def _fused_search_fn(self):
        """jit'd route → fused Pallas kernel (gather+score+top-k in VMEM)."""
        from repro.kernels.ivf_fused import ops as fused_ops
        stages = tuple(self.float_stages)
        scorer = self.scorer
        sim = self.sim
        residual = self.residual
        backend = scorer.name
        use_pallas = not self._fused_reference_only

        @functools.partial(jax.jit, static_argnames=("k", "nprobe"))
        def _search(queries, centroids, list_storage, list_ids, params, *,
                    k, nprobe):
            q = queries
            for t in stages:
                q = t(q, "queries")
            q = q.astype(jnp.float32)
            cscores = similarity(q, centroids, sim)
            cvals, probe = jax.lax.top_k(cscores, nprobe)   # (Q, nprobe)
            extra = cvals if residual else None
            return fused_ops.fused_ivf_topk(probe, q, list_storage,
                                            list_ids, k, backend,
                                            params=params, extra_base=extra,
                                            use_pallas=use_pallas)

        return _search

    def search(self, queries: jax.Array, k: int,
               nprobe: Optional[int] = None, query_chunk: int = 64,
               ) -> tuple[jax.Array, jax.Array]:
        """Top-``min(k, n_docs)`` over the probed lists.

        Slots with no reachable candidate (probed pool < k) come back with
        score ``-inf`` and id ``-1``; with ``nprobe == nlist`` every stored
        doc is reachable and the ranking matches exact search.
        """
        if self.storage is None and self.store is None:
            raise ValueError("IVFIndex is not fitted")
        if self._source is not None and \
                self._source[0]._version != self._source[1]:
            raise ValueError(
                "source CompressedIndex changed since to_ivf (add was "
                "called); the promoted IVF view shares its old storage — "
                "re-promote with to_ivf()")
        nprobe = self._resolve_nprobe(nprobe)
        k = resolve_k(k, self._n_docs)
        if self.storage is None:       # tiered: lists come from the store
            return self._store_search(jnp.asarray(queries), k, nprobe,
                                      query_chunk)
        fused = self._use_fused_kernel
        if fused:
            list_storage, list_ids = self._list_major_layout()
        # k / nprobe are static_argnames: one jit wrapper specializes per
        # (k, nprobe) in its own trace cache
        if self._search_fn is None:
            self._search_fn = (self._fused_search_fn() if fused
                               else self._streaming_search_fn())
        fn = self._search_fn
        queries = jnp.asarray(queries)
        params = self.scorer.params()
        vals_out, idx_out = [], []
        for s in range(0, queries.shape[0], query_chunk):
            qc = queries[s: s + query_chunk]
            if fused:
                v, i = fn(qc, self.centroids, list_storage, list_ids,
                          params, k=k, nprobe=nprobe)
                # from the launched shapes: a device read would sync here
                self.probe_pairs += qc.shape[0] * nprobe
                self.list_steps += min(self.nlist, qc.shape[0] * nprobe)
            else:
                v, i = fn(qc, self.centroids, self.lists, self.storage,
                          params, k=k, nprobe=nprobe)
            vals_out.append(v)
            idx_out.append(i)
        return jnp.concatenate(vals_out), jnp.concatenate(idx_out)

    # -- persistence -------------------------------------------------------
    def state_dict(self) -> dict:
        """Pipeline + storage + router + list layout: the full IVF artifact
        (cold-start search needs no access to the raw corpus)."""
        if self.storage is None and self.store is not None:
            raise ValueError(
                "store-backed (tiered) IVF index has no resident storage to "
                "snapshot — save_index(..., chunked=True) streams it from "
                "the store, or reload with resident='all' first")
        return {"pipeline": self.pipeline.state_dict(),
                "storage": self.storage,
                "centroids": self.centroids,
                "lists": self.lists,
                "labels": self._labels,
                "scorer_extra": self.scorer.extra_state(),
                "nlist": self.nlist,
                "nlist_requested": self._nlist_requested,
                "nprobe": self.nprobe,
                "residual": self.residual,
                "kmeans_init": self.kmeans_init,
                "balanced": self.balanced,
                "n_docs": self._n_docs, "dim": self._dim,
                "version": self._version}

    def load_state_dict(self, sd: dict) -> "IVFIndex":
        self.pipeline.load_state_dict(sd["pipeline"])
        # storage/lists may be None for a tiered load: the caller attaches
        # a ListStore afterwards (repro.retrieval.api._load_index_chunked)
        storage = sd["storage"]
        self.storage = jnp.asarray(storage) if storage is not None else None
        self.centroids = jnp.asarray(sd["centroids"])
        lists = sd["lists"]
        self.lists = jnp.asarray(lists) if lists is not None else None
        labels = sd.get("labels")
        self._labels = (np.asarray(labels) if labels is not None else None)
        self.scorer.load_extra_state(sd.get("scorer_extra", {}))
        self.nlist = int(sd["nlist"])
        self._nlist_requested = int(sd.get("nlist_requested", sd["nlist"]))
        self.nprobe = int(sd["nprobe"])
        self.residual = bool(sd.get("residual", False))
        self.kmeans_init = str(sd.get("kmeans_init", "random"))
        self.balanced = bool(sd.get("balanced", False))
        self._n_docs = int(sd["n_docs"])
        self._dim = int(sd["dim"])
        self._version = int(sd.get("version", 0))
        self._source = None            # an artifact owns its storage
        self._search_fn = None
        self._list_layout = None
        self.store = None
        self._store_fns = None
        return self

    def save(self, path: str) -> None:
        from repro.retrieval.api import save_index
        save_index(self, path)

    @classmethod
    def load(cls, path: str) -> "IVFIndex":
        from repro.retrieval.api import load_index
        return load_index(path, expect=cls)


class IVFFlatIndex(IVFIndex):
    """Float-storage IVF (the seed's FAISS ``IndexIVFFlat`` analogue).

    Thin facade over :class:`IVFIndex` with no compression pipeline — kept
    for the Figure-1 benchmarks and as the uncompressed ANN baseline.
    """

    def __init__(self, nlist: int = 200, nprobe: int = 100, sim: str = "ip",
                 kmeans_iters: int = 15, kmeans_init: str = "random",
                 balanced: bool = False):
        super().__init__(None, nlist=nlist, nprobe=nprobe, sim=sim,
                         backend="jnp", kmeans_iters=kmeans_iters,
                         kmeans_init=kmeans_init, balanced=balanced)

    @property
    def docs(self) -> Optional[jax.Array]:
        return self.storage
