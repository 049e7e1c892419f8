"""Reference recipe ``pca_sign``: the paper's PCA + 1-bit (100×) index.

The shared fit (:mod:`bench.pca_fit`), then one bit per dimension on both
sides: b = (z ≥ 0) on the documents' PCA coordinates and on the queries'
float PCA coordinates.  The score of a (query, document) pair is

    Σ over the first ``dim`` coordinates of (b_q − offset)(b_d − offset)

(``offset`` ½ in the paper), computed at float32 and ``Precision.HIGHEST``;
at offset ½ every score is an exact multiple of ¼.  Only the ``dim``
coordinates are the representation: no pad bit takes part.  The codes are
held as ±1 ``int8`` (2.1M × 245 B = 515 MB at the paper's scale), and the
search runs in blocks of ``block_rows``, the lower id first among ties.
Like the fit, it imports nothing of the program and reads nothing the
program made.

Two findings shape how it is compared with the program's 1-bit index:

* The program's served scores hold the pad bits.  Its packed words round
  245 dimensions up to 256 bits, the documents' pad bits and the queries'
  are both −1, and the kernel's 0.25·(s_q·s_d) counts them: every served
  score is this recipe's plus 11 × ¼ = 2.75 at d = 245 (0 at d = 256).
  Rankings are unaffected; ``score_err`` reads the offset on every row.
* Sign codes flip near zero.  PCA components past the corpus's signal rank
  lie in a near-isotropic noise subspace, fixed only up to rounding, so a
  coordinate near 0 can take the other bit in the program than here (the
  program also projects at the device's default matmul precision).  Each
  such flip moves a score by ½, so the comparison allows a stated number of
  flips, not exact equality.

Its control is the same recipe over the first ``dim // 2`` components.

    params: dim, offset, block_rows (optional)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.pca_fit import HIGHEST, PCAFit, merged_topk, scores_or_nan


@jax.jit
def _signs(z):
    return jnp.where(z >= 0, jnp.int8(1), jnp.int8(-1))


def _values(signs, offset):
    """b − offset of ±1 ``signs`` (b = 1 where the sign is +)."""
    return (signs > 0).astype(jnp.float32) - offset


@functools.partial(jax.jit, static_argnames=("k",))
def _block_topk(vq, codes, offset, start, *, k):
    s = jnp.matmul(vq, _values(codes, offset).T, precision=HIGHEST)
    v, i = jax.lax.top_k(s, k)
    return v, i + start


@jax.jit
def _scores_of(vq, codes, offset, ids):
    vd = _values(codes[jnp.clip(ids, 0, codes.shape[0] - 1)], offset)
    return jnp.einsum("nd,nkd->nk", vq, vd, precision=HIGHEST)


class SignSearch:
    """Exact search over the sign codes of a corpus's PCA encoding.

    ``fit`` needs ``n_docs`` and ``encode(queries)`` (the queries' float
    coordinates); ``codes`` are the documents' ±1 ``int8`` signs."""

    def __init__(self, fit, codes: jax.Array, offset: float,
                 block_rows: int):
        self.fit, self.codes = fit, codes
        self.offset = jnp.float32(offset)
        self.block_rows, self.n_docs = block_rows, fit.n_docs

    def _query_values(self, queries):
        return _values(_signs(self.fit.encode(queries)), self.offset)

    def scores_of(self, queries: np.ndarray, ids: np.ndarray) -> np.ndarray:
        """Reference score of each (query, id); ids outside the corpus get
        NaN."""
        ids = np.asarray(ids)
        out = _scores_of(self._query_values(queries), self.codes,
                         self.offset, jnp.asarray(ids))
        return scores_or_nan(out, ids, self.n_docs)

    def topk(self, queries: np.ndarray, k: int
             ) -> tuple[np.ndarray, np.ndarray]:
        """Exact top-k (scores descending, lower id first among ties)."""
        vq = self._query_values(queries)
        return merged_topk(
            lambda s, e: _block_topk(vq, self.codes[s:e], self.offset,
                                     jnp.int32(s), k=k),
            self.n_docs, self.block_rows, k)


def build(corpus, sample: np.ndarray, *, dim: int, offset: float,
          block_rows: int = 262144) -> SignSearch:
    fit = PCAFit(corpus, sample, dim)
    codes = jnp.concatenate([_signs(z) for z in fit.doc_blocks()])
    return SignSearch(fit, codes, offset, block_rows)


def control(corpus, sample: np.ndarray, *, dim: int, **params
            ) -> SignSearch:
    return build(corpus, sample, dim=dim // 2, **params)
