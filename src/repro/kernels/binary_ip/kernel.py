"""Pallas TPU kernel: 1-bit (sign) index scoring.

GPU implementations of binary similarity use XNOR + popcount.  TPUs have no
popcount path feeding the MXU, so we adapt (DESIGN.md §2): documents live in
HBM **bit-packed** (uint32, d/32 words per vector — the true 32× memory win);
each grid step unpacks one document block to ±1 int8 *in VMEM* and scores it
against a resident query-sign block with an MXU ``int8×int8→int32`` matmul.

Identity: for sign vectors s ∈ {±1}ᵈ and the paper's offset-α encoding
(bit − α), the inner product is an affine function of ``s_q·s_d`` (see
ops.py), so the integer matmul reproduces the paper's 1-bit scoring exactly.

Block shapes are MXU-aligned: (block_q × d) signs, (block_d × d/32) packed
words, (block_q × block_d) int32 out.  d stays resident (d ≤ 4096 after
compression; 768 → 196 KiB per 256-row block — comfortably inside VMEM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.utils import cdiv


def _unpack_block(words: jax.Array, d: int) -> jax.Array:
    """(n, d/32) uint32 → (n, d) int8 signs in {−1, +1} (VMEM-local)."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[:, :, None] >> shifts[None, None, :]) & jnp.uint32(1)
    # the affine map runs in int32: Mosaic cannot multiply i8 vectors
    signs = (bits.astype(jnp.int32) * 2 - 1).astype(jnp.int8)
    return signs.reshape(words.shape[0], d)


def _binary_ip_kernel(q_ref, docs_ref, out_ref, *, d: int):
    """One (block_q, block_d) tile: unpack docs, int8 MXU matmul."""
    signs = _unpack_block(docs_ref[...], d)                  # (bd, d) int8
    q = q_ref[...]                                           # (bq, d) int8
    out_ref[...] = jax.lax.dot_general(
        q, signs,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("block_q", "block_d", "interpret"))
def binary_ip_pallas(q_signs: jax.Array, docs_packed: jax.Array,
                     block_q: int = 128, block_d: int = 512,
                     interpret: bool = False) -> jax.Array:
    """(Q, d) ±1 int8 × (D, d/32) packed uint32 → (Q, D) int32 sign dots.

    Q and D are padded to block multiples internally; d must be a multiple
    of 32 (the encoder pads vectors before packing).
    """
    n_q, d = q_signs.shape
    n_docs, n_words = docs_packed.shape
    if n_words * 32 != d:
        raise ValueError(f"packed width {n_words}*32 != d={d}")

    q_pad = cdiv(n_q, block_q) * block_q - n_q
    d_pad = cdiv(n_docs, block_d) * block_d - n_docs
    q_in = jnp.pad(q_signs, ((0, q_pad), (0, 0))) if q_pad else q_signs
    docs_in = (jnp.pad(docs_packed, ((0, d_pad), (0, 0)))
               if d_pad else docs_packed)

    grid = (q_in.shape[0] // block_q, docs_in.shape[0] // block_d)
    out = pl.pallas_call(
        functools.partial(_binary_ip_kernel, d=d),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_d, n_words), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, block_d), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(
            (q_in.shape[0], docs_in.shape[0]), jnp.int32),
        interpret=interpret,
    )(q_in, docs_in)
    return out[:n_q, :n_docs]
