"""Pallas TPU kernel: fused IVF probe → gather → score → top-k, list-major.

The IVF hot path used to be four HBM round trips (route, gather the probed
lists, score the gathered block, top-k the scores).  Here it is one kernel,
and its grid runs over the batch's *distinct* probed lists rather than over
(query, probe) pairs: the jitted wrapper inverts the (Q, nprobe) probe table
on the device (:func:`invert_probes`) into a scalar-prefetched step table
(``pltpu.PrefetchScalarGridSpec``) of the probed lists, sorted, and a dense
(Q, nlist) correction matrix that is ``-inf`` wherever a row did not probe
a list.  Grid step (c, s) DMAs chunk ``c`` of list ``steps[s]`` once from
the list-major storage, widens it once, scores it against *every* query
row of the batch in one MXU matmul, adds the list's correction column —
which applies the per-(row, probe) term and masks the rows that did not
probe the list in one add — and folds the (Q, Lc) tile into the resident
(Q, k_pad) running top-k.  A list that Q rows probe is fetched and scored
once per batch, not Q times, and neither the gathered candidates nor the
(Q, C) score matrix ever touches HBM.

The in-VMEM merge is the shared sort-free formulation of the ``(score
desc, id asc)`` strict total order
(:func:`repro.retrieval.topk.merge_topk_block`): each of k rounds takes
the max score, breaks ties on the *minimum doc id* among the hits, then
retires that entry.  Because the order is total, merging tile by tile is
associative and exact — rankings are bit-identical to the lexsort
reference (see ref.py and tests/test_ivf_fused.py).

Scoring per backend mirrors the standalone kernels exactly: f32 GEMM
(float / fp16), bf16 pre-scaled × uint8 codes (int8_ip), in-VMEM bit
unpack + int8 sign matmul × 0.25 (binary_ip, offset 0.5).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.retrieval.topk import merge_topk_block
from repro.utils import cdiv

# python scalars, not jnp arrays: the kernel body must not capture tracers
NEG_INF = float("-inf")

BACKENDS = ("float", "fp16", "int8", "onebit")

#: most list rows one grid step scores.  A 64-row batch's (64, 2048) f32
#: tile plus the merge's id and mask copies stays inside the default
#: scoped VMEM; whole 13k-row lists would not.  Longer lists take several
#: steps, the last one ragged: its rows past the list length are masked.
LIST_CHUNK = 2048
ROW_TILE = 8                    # query rows per f32 sublane tile
#: list length the list-major storage is laid out to, once, by its owner.
#: Off this multiple the chunked storage block does not match the array's
#: HBM tiling (uint8 packs 32 rows a tile), and XLA relays out the whole
#: storage — a full copy — ahead of every launch.
LIST_ALIGN = 128


def invert_probes(probes: jax.Array, base: jax.Array, nlist: int,
                  n_rows: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The (Q, nprobe) probe table, turned list-major.

    Returns ``steps`` (S,) int32, the distinct probed lists in ascending
    order, padded to the static length ``S = min(nlist, Q·nprobe)`` by
    repeating the last one; ``n_steps`` (1,) int32, how many are
    distinct; and ``dense`` (n_rows, nlist) f32, ``base[i, j]`` at
    ``(i, probes[i, j])`` and ``-inf`` at every list row ``i`` did not
    probe (rows ``Q..n_rows`` probe nothing).  A row probes a list at most
    once, so the scatter is exact.
    """
    n_q, nprobe = probes.shape
    probes = probes.astype(jnp.int32)
    rows = jax.lax.broadcasted_iota(jnp.int32, probes.shape, 0)
    dense = jnp.full((n_rows, nlist), NEG_INF, jnp.float32)
    dense = dense.at[rows, probes].set(base.astype(jnp.float32))
    probed = jnp.zeros((nlist,), jnp.bool_).at[probes].set(True)
    n_steps = jnp.sum(probed, dtype=jnp.int32)
    size = min(nlist, n_q * nprobe)
    (lists,) = jnp.nonzero(probed, size=size, fill_value=0)
    steps = jnp.where(jnp.arange(size) < n_steps, lists.astype(jnp.int32),
                      jnp.max(probes))
    return steps, n_steps[None], dense


def _unpack_signs(words: jax.Array, d: int) -> jax.Array:
    """(n, d/32) uint32 → (n, d) int8 signs in {−1, +1} (VMEM-local)."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[:, :, None] >> shifts[None, None, :]) & jnp.uint32(1)
    # the affine map runs in int32: Mosaic cannot multiply i8 vectors
    signs = (bits.astype(jnp.int32) * 2 - 1).astype(jnp.int8)
    return signs.reshape(words.shape[0], d)


def score_block(qe: jax.Array, block: jax.Array, backend: str) -> jax.Array:
    """(Q, dq) encoded queries × (L, w) storage block → (Q, L) f32 scores.

    Shared verbatim by the Pallas kernel body and the jnp reference mirror
    (ref.py) so the two paths cannot drift numerically — the parity tests
    require *bitwise* equality.
    """
    if backend in ("float", "fp16"):
        docs = block.astype(jnp.float32)
        return jax.lax.dot_general(
            qe.astype(jnp.float32), docs,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    if backend == "int8":
        # uint8 → int32 → bf16 (exact): Mosaic has no direct uint8 → bf16
        docs = block.astype(jnp.int32).astype(jnp.bfloat16)
        return jax.lax.dot_general(
            qe, docs,                              # qe = (q ⊙ scale) bf16
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    if backend == "onebit":
        signs = _unpack_signs(block, qe.shape[-1])  # (L, d) ±1 int8
        dot = jax.lax.dot_general(
            qe, signs,                             # qe = query signs int8
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)
        return 0.25 * dot.astype(jnp.float32)      # exact for offset 0.5
    raise ValueError(f"unknown fused backend {backend!r}")


def _fused_ivf_kernel(steps_ref, n_steps_ref, qe_ref, dense_ref, storage_ref,
                      ids_ref, out_v_ref, out_i_ref, *, k: int, backend: str,
                      max_len: int):
    """Grid step (c, s): score chunk ``c`` of list ``steps[s]`` against
    every query row and merge it into the rows' running top-k."""
    c, s = pl.program_id(0), pl.program_id(1)

    @pl.when((c == 0) & (s == 0))
    def _init():
        out_v_ref[...] = jnp.full(out_v_ref.shape, NEG_INF, jnp.float32)
        out_i_ref[...] = jnp.full(out_i_ref.shape, -1, jnp.int32)

    @pl.when(s < n_steps_ref[0])        # pad steps repeat the last list
    def _chunk():
        lid = steps_ref[s]
        # the list's id row out of its block of lists (ids are ≥ −1), −1
        # past the list length, where a ragged last chunk reads no row
        id_rows = ids_ref[...]                          # (≤ 8, Lc)
        sub = jax.lax.broadcasted_iota(jnp.int32, id_rows.shape, 0)
        ids = jnp.max(jnp.where(sub == lid % id_rows.shape[0], id_rows, -1),
                      axis=0, keepdims=True)            # (1, Lc)
        pos = c * ids.shape[1] + jax.lax.broadcasted_iota(
            jnp.int32, ids.shape, 1)
        ids = jnp.where(pos < max_len, ids, -1)

        @pl.when(jnp.max(ids) >= 0)     # a chunk past the list's end: skip
        def _score():
            # the list's correction column, −inf for rows that did not
            # probe it
            dense = dense_ref[...]                      # (Q, nlist)
            lane = jax.lax.broadcasted_iota(jnp.int32, dense.shape, 1)
            col = jnp.max(jnp.where(lane == lid, dense, NEG_INF), axis=1,
                          keepdims=True)
            scores = score_block(qe_ref[...], storage_ref[0], backend) + col
            scores = jnp.where(ids >= 0, scores, NEG_INF)
            # a row gains only from a candidate at least its k-th score
            run_v = out_v_ref[...]
            kcol = jax.lax.broadcasted_iota(jnp.int32, run_v.shape, 1)
            kth = jnp.max(jnp.where(kcol == k - 1, run_v, NEG_INF), axis=1,
                          keepdims=True)
            best = jnp.max(scores, axis=1, keepdims=True)
            gain = (best > NEG_INF) & (best >= kth)

            @pl.when(jnp.max(gain.astype(jnp.int32)) > 0)
            def _merge():
                new_v, new_i = merge_topk_block(
                    run_v, out_i_ref[...], scores,
                    jnp.broadcast_to(ids, scores.shape), k)
                out_v_ref[...] = new_v
                out_i_ref[...] = new_i


@functools.partial(jax.jit, static_argnames=("k", "backend", "interpret"))
def fused_ivf_topk_pallas(probes: jax.Array, qe: jax.Array,
                          list_storage: jax.Array, list_ids: jax.Array,
                          base: jax.Array, k: int, backend: str,
                          interpret: bool = False
                          ) -> tuple[jax.Array, jax.Array]:
    """Fused IVF search over probed lists.

    ``probes`` (Q, nprobe) int32 probed list indices, distinct per row;
    ``qe`` (Q, dq) the backend-encoded queries (f32 / bf16·scale / ±1 int8
    signs); ``list_storage`` (nlist, L, w) list-major encoded rows with
    ``list_ids`` (nlist, L) their doc ids (−1 pad); ``base``
    (Q, nprobe) f32 additive score corrections (int8's q·zero term,
    residual encoding's q·centroid term — zeros otherwise).  Returns
    (vals, ids) (Q, k) in (score desc, id asc) order, unreachable slots
    (−inf, −1).
    """
    n_q, nprobe = probes.shape
    nlist, max_len, w = list_storage.shape
    assert list_ids.shape == (nlist, max_len), (list_ids.shape, nlist)
    assert base.shape == (n_q, nprobe), (base.shape, probes.shape)
    if backend not in BACKENDS:
        raise ValueError(f"unknown fused backend {backend!r}")

    lc = min(LIST_CHUNK, max_len)
    n_chunks = cdiv(max_len, lc)
    q_pad = cdiv(n_q, ROW_TILE) * ROW_TILE    # pad rows probe no list
    k_pad = cdiv(k, 128) * 128                # lane-aligned accumulator
    dq = qe.shape[-1]
    n_steps = min(nlist, n_q * nprobe)
    id_rows = min(ROW_TILE, nlist)
    steps, n_distinct, dense = invert_probes(probes, base, nlist, q_pad)
    qe = jnp.pad(qe, ((0, q_pad - n_q), (0, 0)))

    # The grid walks chunk-major, so a pad step repeats the block index of
    # the step before it and Pallas skips its DMA, and the ids of a chunk
    # come in blocks of 8 lists that consecutive steps mostly share.  The
    # queries, the correction matrix and the accumulators stay resident.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_chunks, n_steps),
        in_specs=[
            pl.BlockSpec((q_pad, dq), lambda c, s, st, n: (0, 0)),
            pl.BlockSpec((q_pad, nlist), lambda c, s, st, n: (0, 0)),
            pl.BlockSpec((1, lc, w), lambda c, s, st, n: (st[s], c, 0)),
            pl.BlockSpec((id_rows, lc),
                         lambda c, s, st, n: (st[s] // id_rows, c)),
        ],
        out_specs=[
            pl.BlockSpec((q_pad, k_pad), lambda c, s, st, n: (0, 0)),
            pl.BlockSpec((q_pad, k_pad), lambda c, s, st, n: (0, 0)),
        ],
    )
    vals, ids = pl.pallas_call(
        functools.partial(_fused_ivf_kernel, k=k, backend=backend,
                          max_len=max_len),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((q_pad, k_pad), jnp.float32),
            jax.ShapeDtypeStruct((q_pad, k_pad), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(steps, n_distinct, qe, dense, list_storage, list_ids)
    return vals[:n_q, :k], ids[:n_q, :k]
