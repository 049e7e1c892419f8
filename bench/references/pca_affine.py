"""Reference recipe ``pca_affine``: the paper's PCA + int8 (24×) index.

The shared fit (:mod:`bench.pca_fit`), then per-dimension affine
quantization of the documents' PCA coordinates to ``levels + 1`` levels
(``levels=255`` is int8), and exact inner-product search of the float query
encoding against the decoded codes, at float32 and ``Precision.HIGHEST``.
Like the fit, it imports nothing of the program and reads nothing the
program made.

Its control is the same recipe one precision step lower: int4 codes
(``levels=15``) for int8.

    params: dim, levels, block_rows (optional)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.pca_fit import HIGHEST, PCAFit, merged_topk, scores_or_nan


@functools.partial(jax.jit, static_argnames=("levels",))
def _quantize(z, lo, hi, *, levels):
    scale = jnp.maximum(hi - lo, 1e-12) / levels
    codes = jnp.clip(jnp.round((z - lo) / scale), 0, levels)
    return codes.astype(jnp.uint8), scale


@functools.partial(jax.jit, static_argnames=("k",))
def _block_topk(zq, codes, scale, lo, offset, *, k):
    dec = codes.astype(jnp.float32) * scale + lo
    s = jnp.matmul(zq, dec.T, precision=HIGHEST)
    v, i = jax.lax.top_k(s, k)
    return v, i + offset


@jax.jit
def _scores_of(zq, codes, scale, lo, ids):
    dec = codes[jnp.clip(ids, 0, codes.shape[0] - 1)].astype(jnp.float32) \
        * scale + lo                                       # (n, k, dim)
    return jnp.einsum("nd,nkd->nk", zq, dec, precision=HIGHEST)


class AffineSearch:
    """Exact search over the affine-quantized PCA encoding of a corpus."""

    def __init__(self, fit: PCAFit, levels: int, block_rows: int):
        z = jnp.concatenate(list(fit.doc_blocks()))
        self.lo, hi = jnp.min(z, axis=0), jnp.max(z, axis=0)
        self.codes, self.scale = _quantize(z, self.lo, hi, levels=levels)
        del z
        self.fit, self.block_rows, self.n_docs = fit, block_rows, fit.n_docs

    def scores_of(self, queries: np.ndarray, ids: np.ndarray) -> np.ndarray:
        """Reference score of each (query, id); ids outside the corpus get
        NaN."""
        ids = np.asarray(ids)
        out = _scores_of(self.fit.encode(queries), self.codes, self.scale,
                         self.lo, jnp.asarray(ids))
        return scores_or_nan(out, ids, self.n_docs)

    def topk(self, queries: np.ndarray, k: int
             ) -> tuple[np.ndarray, np.ndarray]:
        """Exact top-k (scores descending, lower id first among ties)."""
        zq = self.fit.encode(queries)
        return merged_topk(
            lambda s, e: _block_topk(zq, self.codes[s:e], self.scale,
                                     self.lo, jnp.int32(s), k=k),
            self.n_docs, self.block_rows, k)


def build(corpus, sample: np.ndarray, *, dim: int, levels: int,
          block_rows: int = 262144) -> AffineSearch:
    return AffineSearch(PCAFit(corpus, sample, dim), levels, block_rows)


def control(corpus, sample: np.ndarray, *, levels: int, **params
            ) -> AffineSearch:
    """int4 codes (``levels=15``) in place of int8 (``levels=255``)."""
    if levels != 255:
        raise ValueError(f"pca_affine's control is int4 for int8 "
                         f"(levels=255); this configuration has "
                         f"levels={levels}")
    return build(corpus, sample, levels=15, **params)
