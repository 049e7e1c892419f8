"""Fused IVF hot path: interpret-mode Pallas vs jnp-reference parity across
all scorer backends (alone and under SegmentedIndex delta layers), streaming
blockwise top-k properties, and the recall satellites (residual encoding,
learned rotation, kmeans++ / balanced lists)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st

from repro.core import (CenterNorm, CompressionPipeline, LearnedRotation,
                        OneBitQuantizer, PCA, build_method)
from repro.data import make_dpr_like_kb
from repro.kernels.ivf_fused import ops as fused_ops
from repro.kernels.ivf_fused.kernel import (LIST_ALIGN, LIST_CHUNK,
                                           invert_probes)
from repro.retrieval import (CompressedIndex, IVFIndex, SegmentedIndex,
                             backend_tail_stages, recall_at_k)
from repro.retrieval.kmeans import assign, assign_balanced, kmeans_fit
from repro.retrieval.topk import (masked_topk_by_id, resolve_nprobe,
                                  similarity, streaming_masked_topk)
from repro.serve import ServeEngine

BACKENDS = tuple(backend_tail_stages())


@pytest.fixture(scope="module")
def kb():
    return make_dpr_like_kb(n_queries=32, n_docs=1200, d=64, r_eff=24)


def _build_fused(kb, backend, **kw):
    tail = backend_tail_stages()[backend]
    pipe = CompressionPipeline([CenterNorm(), PCA(32)] + tail)
    idx = IVFIndex.build(kb.docs, kb.queries, pipe, nlist=24, nprobe=6,
                         backend="pallas", kmeans_iters=6, **kw)
    assert idx._use_fused_kernel
    return idx


def _ref_search(idx, queries, k, nprobe=None):
    """Same index, searched through the interpret-mode jnp reference."""
    idx._fused_reference_only = True
    idx._search_fn = None
    try:
        return idx.search(queries, k, nprobe=nprobe)
    finally:
        idx._fused_reference_only = False
        idx._search_fn = None


# ---------------------------------------------------------------------------
# fused kernel ≡ reference, bitwise, per backend and at any nprobe
# ---------------------------------------------------------------------------


# query batches: 16 distinct rows; 4 rows repeated 4 times, so that rows
# share every list they probe; all 32 rows, which together probe every
# list; 13 rows, which the kernel pads to 16
BATCHES = {"16": lambda q: q[:16], "shared": lambda q: jnp.tile(q[:4], (4, 1)),
           "every_list": lambda q: q, "13": lambda q: q[:13]}


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("nprobe,batch", [
    pytest.param(1, "16", id="1"), pytest.param(5, "16", id="5"),
    pytest.param(24, "16", id="24"),
    pytest.param(5, "shared", id="5-shared"),
    pytest.param(6, "every_list", id="6-every_list"),
    pytest.param(5, "13", id="5-rows13")])
def test_fused_matches_reference_bitwise(kb, backend, nprobe, batch):
    """The fused Pallas kernel (interpret mode on CPU) must reproduce the
    jnp reference mirror *bit-identically* — both ids and scores — for
    every scorer backend, from a single probed list up to full probe, and
    whether the batch's rows share lists or not."""
    idx = _build_fused(kb, backend)
    q = BATCHES[batch](kb.queries)
    if batch == "every_list":
        cs = similarity(idx.encode_queries(q), idx.centroids, idx.sim)
        probes = np.asarray(jax.lax.top_k(cs, nprobe)[1])
        assert np.unique(probes).size == idx.nlist
    vals_p, ids_p = idx.search(q, 10, nprobe=nprobe)
    vals_r, ids_r = _ref_search(idx, q, 10, nprobe=nprobe)
    np.testing.assert_array_equal(np.asarray(ids_p), np.asarray(ids_r))
    np.testing.assert_array_equal(np.asarray(vals_p), np.asarray(vals_r))


def _synthetic_lists(backend, nlist, lens, max_len, d, rng):
    """List-major storage of ``backend`` rows, ids distinct across lists
    and −1 past each list's length, plus the backend's query params."""
    if backend == "onebit":
        w = d // 32
        storage = rng.integers(0, 2**32, (nlist, max_len, w), np.uint32)
        params = {}
    elif backend == "int8":
        storage = rng.integers(0, 256, (nlist, max_len, d), np.uint8)
        params = {"scale": jnp.asarray(rng.uniform(0.01, 0.02, d),
                                       jnp.float32),
                  "zero": jnp.asarray(rng.standard_normal(d), jnp.float32)}
    else:
        dtype = np.float16 if backend == "fp16" else np.float32
        storage = rng.standard_normal((nlist, max_len, d)).astype(dtype)
        params = {}
    pos = np.arange(max_len)[None, :]
    ids = np.where(pos < np.asarray(lens)[:, None],
                   np.arange(nlist)[:, None] * max_len + pos, -1)
    return jnp.asarray(storage), jnp.asarray(ids, jnp.int32), params


@pytest.mark.parametrize("backend", BACKENDS)
def test_fused_chunked_lists_match_reference(backend):
    """Lists longer than one grid step are scored chunk by chunk, the last
    chunk ragged: with a full list, an empty one, one that ends on a chunk
    boundary, two that end inside a chunk, a row count that is no
    multiple of 8 and a per-(row, probe) correction, the kernel still
    matches the reference bitwise."""
    rng = np.random.default_rng(11)
    nlist, nprobe, n_q, d = 5, 3, 10, 64
    max_len = 2 * LIST_CHUNK + 604
    lens = [max_len, 0, LIST_CHUNK, 1500, 2 * LIST_CHUNK + 3]
    storage, ids, params = _synthetic_lists(backend, nlist, lens, max_len,
                                            d, rng)
    q = jnp.asarray(rng.standard_normal((n_q, d)), jnp.float32)
    probes = jnp.asarray(np.argsort(rng.random((n_q, nlist)), axis=1)
                         [:, :nprobe], jnp.int32)
    extra = jnp.asarray(rng.standard_normal((n_q, nprobe)), jnp.float32)
    got = fused_ops.fused_ivf_topk(probes, q, storage, ids, 10, backend,
                                   params=params, extra_base=extra,
                                   interpret=True)
    want = fused_ops.fused_ivf_topk(probes, q, storage, ids, 10, backend,
                                    params=params, extra_base=extra,
                                    use_pallas=False)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    assert np.all(np.asarray(got[1]) >= 0)


def test_invert_probes_step_table_and_corrections():
    """The step table is the sorted distinct probed lists, padded to
    min(nlist, Q·nprobe) by repeating the last; the dense corrections are
    −inf exactly where a row did not probe a list, the row's correction
    where it did, and −inf on every pad row."""
    probes = jnp.asarray([[4, 1], [1, 5], [4, 5]], jnp.int32)
    base = jnp.asarray([[0.5, -0.0], [2.0, 3.0], [-1.5, 7.25]], jnp.float32)
    steps, n_steps, dense = invert_probes(probes, base, nlist=6, n_rows=8)
    np.testing.assert_array_equal(np.asarray(steps), [1, 4, 5, 5, 5, 5])
    assert np.asarray(n_steps).tolist() == [3]
    want = np.full((8, 6), -np.inf, np.float32)
    for i, row in enumerate(np.asarray(probes)):
        want[i, row] = np.asarray(base)[i]
    np.testing.assert_array_equal(np.asarray(dense), want)
    assert np.signbit(np.asarray(dense)[0, 1])       # −0.0 kept exactly
    # fewer probe pairs than lists: the table is Q·nprobe long, no pad
    steps, n_steps, _ = invert_probes(jnp.asarray([[7, 2, 9]], jnp.int32),
                                      jnp.zeros((1, 3)), nlist=10, n_rows=8)
    np.testing.assert_array_equal(np.asarray(steps), [2, 7, 9])
    assert np.asarray(n_steps).tolist() == [3]


def test_list_major_layout_is_aligned_once(kb):
    """The list-major copy pads ``max_len`` to ``LIST_ALIGN`` with −1 ids
    (zero rows), holds each list's ids in order, and is built once per
    index version, not per search."""
    idx = _build_fused(kb, "int8")
    storage, ids = idx._list_major_layout()
    n_lists, max_len = idx.lists.shape
    assert ids.shape == (n_lists, max_len + -max_len % LIST_ALIGN)
    assert storage.shape[:2] == ids.shape
    np.testing.assert_array_equal(np.asarray(ids[:, :max_len]),
                                  np.asarray(idx.lists))
    assert np.all(np.asarray(ids[:, max_len:]) == -1)
    assert not np.asarray(storage)[np.asarray(ids) < 0].any()
    idx.search(kb.queries[:4], 5, nprobe=3)
    assert idx._list_major_layout()[1] is ids


def test_fused_counters(kb):
    """``probe_pairs`` sums rows × nprobe over fused launches and
    ``list_steps`` the launched list steps, at most min(nlist, rows ×
    nprobe) a launch; the serving engine reports both."""
    idx = _build_fused(kb, "int8")
    engine = ServeEngine(idx, k=5)
    assert engine.stats()["probe_pairs"] == engine.stats()["list_steps"] == 0
    calls = [(1, 6), (16, 3), (13, 24), (32, 6)]
    for rows, nprobe in calls:
        idx.search(kb.queries[:rows], 5, nprobe=nprobe)
    stats = engine.stats()
    assert stats["probe_pairs"] == sum(r * p for r, p in calls)
    assert stats["list_steps"] == sum(min(idx.nlist, r * p) for r, p in calls)
    assert stats["list_steps"] < stats["probe_pairs"]
    # a segmented index reports its IVF main's counters
    assert ServeEngine(SegmentedIndex(idx), k=5).stats()["probe_pairs"] == \
        stats["probe_pairs"]


@pytest.mark.parametrize("backend", BACKENDS)
def test_fused_agrees_with_jnp_path(kb, backend):
    """Cross-path sanity: the fused kernel ranks (nearly) the same docs as
    the streaming jnp path on the same fitted index.  Exact id equality is
    *not* required here — int8 scores in bf16 inside the kernel while the
    jnp oracle decodes to f32, so near-ties may flip — but scores must
    agree to tolerance and the candidate sets must overlap heavily."""
    idx = _build_fused(kb, backend)
    jnp_view = IVFIndex(idx.pipeline, nlist=idx.nlist, nprobe=idx.nprobe,
                        backend="jnp")
    jnp_view.load_state_dict(idx.state_dict())
    q = kb.queries[:16]
    vals_p, ids_p = idx.search(q, 10, nprobe=8)
    vals_j, ids_j = jnp_view.search(q, 10, nprobe=8)
    np.testing.assert_allclose(np.asarray(vals_p), np.asarray(vals_j),
                               rtol=1e-2, atol=1e-2)
    overlap = np.mean([len(set(a) & set(b)) / len(a)
                       for a, b in zip(np.asarray(ids_p), np.asarray(ids_j))])
    assert overlap >= 0.9


def test_fused_full_probe_matches_exact(kb):
    """nprobe == nlist through the fused float kernel reproduces exact
    search rankings (every doc reachable, shared tie order)."""
    pipe = CompressionPipeline([CenterNorm(), PCA(32)])
    exact = CompressedIndex.build(kb.docs, kb.queries, pipe, backend="jnp")
    _, want = exact.search(kb.queries[:16], 10)
    ivf = IVFIndex(pipe, nlist=16, nprobe=16, backend="pallas",
                   kmeans_iters=6)
    ivf.fit(kb.docs)
    assert ivf._use_fused_kernel
    _, got = ivf.search(kb.queries[:16], 10)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# parity through SegmentedIndex delta layers
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("nprobe", [3, 20])
def test_segmented_delta_parity(kb, backend, nprobe):
    """Fused vs reference stays bit-identical when the IVF main sits under
    SegmentedIndex delta segments and tombstones: the delta layer scores
    through the same jnp path either way, so any divergence isolates the
    kernel."""
    base = np.asarray(kb.docs)
    tail = backend_tail_stages()[backend]
    pipe = CompressionPipeline([CenterNorm(), PCA(32)] + tail)
    main = IVFIndex.build(base[:1000], kb.queries, pipe, nlist=20, nprobe=6,
                          backend="pallas", kmeans_iters=6)
    assert main._use_fused_kernel
    seg = SegmentedIndex(main)
    seg.add(base[1000:1100])
    seg.add(base[1100:])
    seg.delete([3, 17, 1005])
    q = kb.queries[:16]
    vals_p, ids_p = seg.search(q, 10, nprobe=nprobe)
    main._fused_reference_only = True
    main._search_fn = None
    try:
        vals_r, ids_r = seg.search(q, 10, nprobe=nprobe)
    finally:
        main._fused_reference_only = False
        main._search_fn = None
    np.testing.assert_array_equal(np.asarray(ids_p), np.asarray(ids_r))
    np.testing.assert_array_equal(np.asarray(vals_p), np.asarray(vals_r))


# ---------------------------------------------------------------------------
# streaming blockwise top-k ≡ monolithic top-k (any block size)
# ---------------------------------------------------------------------------


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 60), st.integers(1, 12), st.integers(1, 4),
       st.integers(0, 10_000))
def test_streaming_topk_matches_monolithic(block, k, n_q, seed):
    """The strict (score desc, id asc) order is total, so folding blocks
    into a running top-k is associative: any block size must reproduce the
    monolithic result exactly, pads and −inf included."""
    rng = np.random.default_rng(seed)
    n = 37
    s = rng.standard_normal((n_q, n)).astype(np.float32)
    ids = rng.integers(0, 500, (n_q, n)).astype(np.int32)
    s[rng.random((n_q, n)) < 0.2] = -np.inf     # invalid / padded slots
    want_v, want_i = masked_topk_by_id(jnp.asarray(s), jnp.asarray(ids), k)
    got_v, got_i = streaming_masked_topk(jnp.asarray(s), jnp.asarray(ids),
                                         k, block)
    np.testing.assert_array_equal(np.asarray(want_i), np.asarray(got_i))
    np.testing.assert_array_equal(np.asarray(want_v), np.asarray(got_v))


@pytest.mark.parametrize("block", [1, 2, 3, 5, 8, 36, 37, 50])
def test_streaming_topk_block_sweep(block):
    """Deterministic counterpart of the hypothesis property (runs even
    without hypothesis installed): every block size, including 1, a
    non-divisor, the exact width, and an over-width block."""
    rng = np.random.default_rng(7)
    s = rng.standard_normal((3, 37)).astype(np.float32)
    ids = rng.integers(0, 200, (3, 37)).astype(np.int32)
    s[rng.random((3, 37)) < 0.25] = -np.inf
    want_v, want_i = masked_topk_by_id(jnp.asarray(s), jnp.asarray(ids), 9)
    got_v, got_i = streaming_masked_topk(jnp.asarray(s), jnp.asarray(ids),
                                         9, block)
    np.testing.assert_array_equal(np.asarray(want_i), np.asarray(got_i))
    np.testing.assert_array_equal(np.asarray(want_v), np.asarray(got_v))


def test_streaming_topk_rejects_bad_block():
    s = jnp.zeros((2, 8))
    ids = jnp.arange(16, dtype=jnp.int32).reshape(2, 8)
    with pytest.raises(ValueError, match="block"):
        streaming_masked_topk(s, ids, 3, 0)


def test_resolve_nprobe_semantics():
    assert resolve_nprobe(None, 16, default=7) == 7
    assert resolve_nprobe(100, 16) == 16           # clamps to nlist
    assert resolve_nprobe(3, 16) == 3
    with pytest.raises(ValueError, match="nprobe must be ≥ 1"):
        resolve_nprobe(0, 16)


# ---------------------------------------------------------------------------
# residual encoding
# ---------------------------------------------------------------------------


def test_residual_float_full_probe_is_exact(kb):
    """Float residual storage is mathematically exact: q·(x−c) + q·c = q·x,
    so full probe must reproduce exact search bit-for-bit on the jnp path
    and id-for-id on the fused path."""
    pipe = CompressionPipeline([CenterNorm(), PCA(32)])
    exact = CompressedIndex.build(kb.docs, kb.queries, pipe, backend="jnp")
    _, want = exact.search(kb.queries[:16], 10)
    for backend in ("jnp", "pallas"):
        ivf = IVFIndex(pipe, nlist=16, nprobe=16, backend=backend,
                       kmeans_iters=6, residual=True)
        ivf.fit(kb.docs)
        _, got = ivf.search(kb.queries[:16], 10)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_residual_quantized_search_and_roundtrip(kb):
    """Quantized residual IVF searches, persists, and survives add()."""
    pipe = CompressionPipeline([CenterNorm(), PCA(32), OneBitQuantizer(0.5)])
    ivf = IVFIndex(pipe, nlist=16, nprobe=8, backend="jnp", kmeans_iters=6,
                   residual=True)
    base = np.asarray(kb.docs)
    pipe.fit(base[:1000], kb.queries)
    ivf.fit(base[:1000])
    v0, i0 = ivf.search(kb.queries[:8], 5)
    assert np.all(np.asarray(i0) >= 0)
    sd = ivf.state_dict()
    ivf2 = IVFIndex(pipe, backend="jnp").load_state_dict(sd)
    assert ivf2.residual
    v1, i1 = ivf2.search(kb.queries[:8], 5)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    ivf.add(base[1000:])
    v2, i2 = ivf.search(kb.queries[:8], 5)
    assert len(ivf) == base.shape[0]
    assert np.all(np.asarray(i2) >= 0)


def test_residual_guards(kb):
    with pytest.raises(ValueError, match="IP-only"):
        IVFIndex(None, sim="l2", residual=True)
    pipe = CompressionPipeline([CenterNorm(), PCA(32)])
    ivf = IVFIndex(pipe, nlist=8, backend="jnp", residual=True)
    pipe.fit(kb.docs, kb.queries)
    x = pipe(kb.docs, "docs")
    with pytest.raises(ValueError, match="pre-encoded"):
        ivf._install(x, x)
    ivf.fit(kb.docs)
    with pytest.raises(TypeError, match="residual"):
        SegmentedIndex(ivf)


# ---------------------------------------------------------------------------
# learned rotation (OPQ-style) before 1-bit quantization
# ---------------------------------------------------------------------------


def test_learned_rotation_is_orthogonal_and_ip_preserving(kb):
    rot = LearnedRotation(n_iters=5)
    rot.fit(kb.docs)
    r = np.asarray(rot.state["rotation"])
    np.testing.assert_allclose(r @ r.T, np.eye(r.shape[0]), atol=1e-4)
    q = np.asarray(kb.queries[:8], np.float32)
    x = np.asarray(kb.docs[:64], np.float32)
    want = q @ x.T
    got = np.asarray(rot(jnp.asarray(q), "queries")) @ \
        np.asarray(rot(jnp.asarray(x), "docs")).T
    np.testing.assert_allclose(got, want, atol=1e-3)


def test_pca_rot_onebit_recall_at_least_pca_onebit():
    """The registry's pca_rot_onebit method must not lose recall vs plain
    pca_onebit — the rotation re-aims the sign grid after PCA concentrates
    variance on few axes, and is free at search time (orthogonal)."""
    kb = make_dpr_like_kb(n_queries=48, n_docs=2500, d=64, r_eff=24)
    from repro.retrieval import DenseIndex
    dense = DenseIndex(kb.docs)
    _, want = dense.search(kb.queries, 10)
    recalls = {}
    for method in ("pca_onebit", "pca_rot_onebit"):
        pipe = build_method(method, dim=24, post=False)
        idx = CompressedIndex.build(kb.docs, kb.queries, pipe, backend="jnp")
        _, got = idx.search(kb.queries, 10)
        recalls[method] = recall_at_k(got, want)
    assert recalls["pca_rot_onebit"] >= recalls["pca_onebit"]


# ---------------------------------------------------------------------------
# kmeans++ seeding and balanced list assignment
# ---------------------------------------------------------------------------


def test_kmeanspp_seeding_shapes_and_guard():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((600, 16)), jnp.float32)
    c = kmeans_fit(x, 12, 5, jax.random.PRNGKey(0), init="++")
    assert c.shape == (12, 16)
    assert bool(jnp.all(jnp.isfinite(c)))
    with pytest.raises(ValueError, match="init"):
        kmeans_fit(x, 4, 2, init="nope")


def test_balanced_assignment_caps_list_skew():
    rng = np.random.default_rng(3)
    # deliberately skewed corpus: one heavy cluster plus background noise
    heavy = rng.standard_normal((1500, 32)) * 0.05 + 2.0
    rest = rng.standard_normal((1500, 32))
    x = jnp.asarray(np.concatenate([heavy, rest]), jnp.float32)
    c = kmeans_fit(x, 16, 8, jax.random.PRNGKey(0))
    plain = np.bincount(np.asarray(assign(x, c)), minlength=16)
    bal = np.bincount(np.asarray(assign_balanced(x, c)), minlength=16)
    assert bal.sum() == plain.sum() == x.shape[0]
    assert bal.max() <= plain.max()


def test_ivf_with_quality_options_full_probe_exact(kb):
    """kmeans++ + balanced lists change *which* list holds a doc, never
    which docs are reachable at full probe: still exact."""
    pipe = CompressionPipeline([CenterNorm(), PCA(32)])
    exact = CompressedIndex.build(kb.docs, kb.queries, pipe, backend="jnp")
    _, want = exact.search(kb.queries[:16], 10)
    ivf = IVFIndex(pipe, nlist=16, nprobe=16, backend="jnp", kmeans_iters=6,
                   kmeans_init="++", balanced=True)
    ivf.fit(kb.docs)
    _, got = ivf.search(kb.queries[:16], 10)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    sd = ivf.state_dict()
    assert sd["kmeans_init"] == "++" and sd["balanced"]
