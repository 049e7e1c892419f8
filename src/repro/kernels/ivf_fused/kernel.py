"""Pallas TPU kernel: fused IVF probe → gather → score → top-k.

The IVF hot path used to be four HBM round trips (route, gather the probed
lists, score the gathered block, top-k the scores).  Here it is one kernel:
the (Q, nprobe) probe table is *scalar-prefetched*
(``pltpu.PrefetchScalarGridSpec``) so the BlockSpec index maps are
data-dependent — grid step (i, j) DMAs inverted list ``probes[i, j]``
straight from the list-major storage into VMEM, scores it against query
``i``'s resident block with the backend's MXU path, and folds the block
into query ``i``'s running top-k accumulator.  Neither the gathered
``(Q, nprobe, max_len, w)`` intermediate nor the (Q, C) score matrix ever
touches HBM.

The in-VMEM merge is the shared sort-free formulation of the ``(score
desc, id asc)`` strict total order
(:func:`repro.retrieval.topk.merge_topk_block`): each of k rounds takes
the max score, breaks ties on the *minimum doc id* among the hits, then
retires that entry.  Because the order is total, merging list-by-list is
associative and exact — rankings are bit-identical to the monolithic
lexsort reference (see ref.py and tests/test_ivf_fused.py).

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


def _unpack_signs(words: jax.Array, d: int) -> jax.Array:
    """(n, d/32) uint32 → (n, d) int8 signs in {−1, +1} (VMEM-local)."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[:, :, None] >> shifts[None, None, :]) & jnp.uint32(1)
    # the affine map runs in int32: Mosaic cannot multiply i8 vectors
    signs = (bits.astype(jnp.int32) * 2 - 1).astype(jnp.int8)
    return signs.reshape(words.shape[0], d)


def score_block(qe: jax.Array, block: jax.Array, backend: str) -> jax.Array:
    """(1, dq) encoded query × (L, w) storage block → (1, L) f32 scores.

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


def _fused_ivf_kernel(probes_ref, qe_ref, storage_ref, ids_ref, base_ref,
                      out_v_ref, out_i_ref, *, k: int, backend: str):
    """Grid step (i, j): score list ``probes[i, j]`` for query ``i`` and
    merge it into query ``i``'s running top-k accumulator."""
    del probes_ref  # consumed by the BlockSpec index maps

    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_v_ref[...] = jnp.full(out_v_ref.shape, NEG_INF, jnp.float32)
        out_i_ref[...] = jnp.full(out_i_ref.shape, -1, jnp.int32)

    ids = ids_ref[...]                                  # (1, L) int32, −1 pad
    s = score_block(qe_ref[...], storage_ref[0], backend)
    s = s + base_ref[0, j]                              # rank-1 corrections
    s = jnp.where(ids >= 0, s, NEG_INF)
    run_v, run_i = merge_topk_block(out_v_ref[...], out_i_ref[...],
                                    s, jnp.where(ids >= 0, ids, -1), k)
    out_v_ref[...] = run_v
    out_i_ref[...] = run_i


@functools.partial(jax.jit, static_argnames=("k", "backend", "interpret"))
def fused_ivf_topk_pallas(probes: jax.Array, qe: jax.Array,
                          list_storage: jax.Array, list_ids: jax.Array,
                          base: jax.Array, k: int, backend: str,
                          interpret: bool = False
                          ) -> tuple[jax.Array, jax.Array]:
    """Fused IVF search over probed lists.

    ``probes`` (Q, nprobe) int32 probed list indices; ``qe`` (Q, dq) the
    backend-encoded queries (f32 / bf16·scale / ±1 int8 signs);
    ``list_storage`` (nlist, L, w) list-major encoded rows with ``list_ids``
    (nlist, L) their doc ids (−1 pad); ``base`` (Q, nprobe) f32 additive
    score corrections (int8's q·zero term, residual encoding's q·centroid
    term — zeros otherwise).  Returns (vals, ids) (Q, k) in (score desc,
    id asc) order, unreachable slots (−inf, −1).
    """
    n_q, nprobe = probes.shape
    nlist, max_len, _ = list_storage.shape
    assert list_ids.shape == (nlist, max_len), (list_ids.shape, nlist)
    assert base.shape == (n_q, nprobe), (base.shape, probes.shape)
    if backend not in BACKENDS:
        raise ValueError(f"unknown fused backend {backend!r}")

    k_pad = cdiv(k, 128) * 128        # lane-aligned accumulator width
    dq = qe.shape[-1]
    w = list_storage.shape[-1]

    # Per-query and per-list rows get a unit middle axis so each block's
    # last two dims equal the array's (Mosaic's (8, 128) tiling rule); the
    # squeezed leading axis hands the kernel the same 2-D refs.  ``base``
    # is read as scalars, one SMEM row per query.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_q, nprobe),
        in_specs=[
            pl.BlockSpec((pl.squeezed, 1, dq), lambda i, j, p: (i, 0, 0)),
            pl.BlockSpec((1, max_len, w), lambda i, j, p: (p[i, j], 0, 0)),
            pl.BlockSpec((pl.squeezed, 1, max_len),
                         lambda i, j, p: (p[i, j], 0, 0)),
            pl.BlockSpec((pl.squeezed, 1, nprobe), lambda i, j, p: (i, 0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((pl.squeezed, 1, k_pad), lambda i, j, p: (i, 0, 0)),
            pl.BlockSpec((pl.squeezed, 1, k_pad), lambda i, j, p: (i, 0, 0)),
        ],
    )
    vals, ids = pl.pallas_call(
        functools.partial(_fused_ivf_kernel, k=k, backend=backend),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_q, 1, k_pad), jnp.float32),
            jax.ShapeDtypeStruct((n_q, 1, k_pad), jnp.int32),
        ],
        interpret=interpret,
    )(probes.astype(jnp.int32), qe[:, None, :], list_storage,
      list_ids[:, None, :], base.astype(jnp.float32)[:, None, :])
    return vals[:, 0, :k], ids[:, 0, :k]
