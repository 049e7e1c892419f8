"""Pure-jnp reference for the fused IVF kernel — bitwise oracle.

Runs the *same* per-tile score math as the kernel (``kernel.score_block``
on the same (Q_pad, dq) × (Lc, w) shapes, chunk-major over the lists in
ascending order), but takes each list's correction straight from the
probe table rather than from :func:`~repro.kernels.ivf_fused.kernel.
invert_probes`, walks every list (a list no row probed merges nothing),
and merges with the shared lexsort :func:`~repro.retrieval.topk.
masked_topk_by_id`.  Because (score desc, id asc) is a strict total order
the two merge formulations are equivalent, so the parity tests can demand
exact id *and* value equality against the interpret-mode kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ivf_fused.kernel import LIST_CHUNK, ROW_TILE, score_block
from repro.retrieval.topk import masked_topk_by_id
from repro.utils import cdiv


@functools.partial(jax.jit, static_argnames=("k", "backend"))
def fused_ivf_topk_ref(probes: jax.Array, qe: jax.Array,
                       list_storage: jax.Array, list_ids: jax.Array,
                       base: jax.Array, k: int, backend: str
                       ) -> tuple[jax.Array, jax.Array]:
    """Same contract as ``kernel.fused_ivf_topk_pallas`` (Q, k) outputs."""
    n_q = probes.shape[0]
    nlist, max_len, w = list_storage.shape
    lc = min(LIST_CHUNK, max_len)
    n_chunks = cdiv(max_len, lc)
    pad = ((0, cdiv(n_q, ROW_TILE) * ROW_TILE - n_q), (0, 0))
    qe = jnp.pad(qe, pad)                    # the kernel's padded row block
    probes = jnp.pad(probes, pad, constant_values=-1)   # pad rows probe none
    base = jnp.pad(base.astype(jnp.float32), pad)
    # the kernel's ragged last chunk, as rows that hold no doc
    tail = n_chunks * lc - max_len
    list_storage = jnp.pad(list_storage, ((0, 0), (0, tail), (0, 0)))
    list_ids = jnp.pad(list_ids, ((0, 0), (0, tail)), constant_values=-1)

    def step(carry, t):
        c, lid = t // nlist, t % nlist
        col = jnp.max(jnp.where(probes == lid, base, -jnp.inf), axis=1,
                      keepdims=True)
        block = jax.lax.dynamic_slice(list_storage, (lid, c * lc, 0),
                                      (1, lc, w))[0]
        ids = jax.lax.dynamic_slice(list_ids, (lid, c * lc), (1, lc))
        s = score_block(qe, block, backend) + col
        s = jnp.where(ids >= 0, s, -jnp.inf)
        rv, ri = carry
        cv = jnp.concatenate([rv, s], axis=1)
        ci = jnp.concatenate([ri, jnp.broadcast_to(ids, s.shape)], axis=1)
        return masked_topk_by_id(cv, ci, k), None

    init = (jnp.full((qe.shape[0], k), -jnp.inf, jnp.float32),
            jnp.full((qe.shape[0], k), -1, jnp.int32))
    (vals, ids), _ = jax.lax.scan(step, init,
                                  jnp.arange(n_chunks * nlist))
    return vals[:n_q], ids[:n_q]
