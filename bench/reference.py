"""The plain reference that decides ``correct``.

It computes what the configuration states from the corpus alone — center
and L2-normalize each population, PCA to ``dim`` components fitted on the
documents, per-dimension affine quantization to ``levels + 1`` levels of the
documents, exact inner-product search of the float query encoding against
the decoded codes — in straightforward ``jax.numpy`` at float32 and
``Precision.HIGHEST``.  It imports nothing of the program and reads nothing
the program made: no pipeline state, codebook, centroid or code.  The
documents are regenerated on the device from the seed, block by block, so
the reference runs after the program's state has been freed.

``levels=255`` is the configuration's int8 recipe; ``levels=15`` is the
control, the same recipe one precision step lower (int4).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _normalize(x):
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


@jax.jit
def _sum_rows(rows):
    return jnp.sum(rows, axis=0)


@jax.jit
def _moments(rows, mean_docs):
    x = _normalize(rows - mean_docs)
    return jnp.sum(x, axis=0), jnp.matmul(x.T, x, precision=HIGHEST)


@jax.jit
def _project(rows, mean_docs, mean_x, w):
    x = _normalize(rows - mean_docs)
    return jnp.matmul(x - mean_x, w, precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("dim",))
def _components(s, ss, n, *, dim):
    mean = s / n
    cov = ss / n - jnp.outer(mean, mean)
    evals, evecs = jnp.linalg.eigh(cov)
    order = jnp.argsort(-evals)[:dim]
    return mean, evecs[:, order]


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


class Reference:
    """Exact search over the quantized PCA encoding of a corpus.

    ``corpus`` yields ``(start, stop, rows)`` device blocks from
    :meth:`blocks` (rows past ``stop − start`` are not the corpus's);
    ``sample`` is the query sample the configuration's index is fitted
    with (its mean centers the queries).
    """

    def __init__(self, corpus, sample: np.ndarray, dim: int,
                 levels: int = 255, block_rows: int = 262144):
        n = corpus.spec.n_docs
        s = jnp.zeros((corpus.spec.d,), jnp.float32)
        for start, stop, rows in corpus.blocks():
            s = s + _sum_rows(rows[: stop - start])
        self.mean_docs = s / n
        s = jnp.zeros((corpus.spec.d,), jnp.float32)
        ss = jnp.zeros((corpus.spec.d, corpus.spec.d), jnp.float32)
        for start, stop, rows in corpus.blocks():
            bs, bss = _moments(rows[: stop - start], self.mean_docs)
            s, ss = s + bs, ss + bss
        self.mean_x, self.w = _components(s, ss, jnp.float32(n), dim=dim)
        z = jnp.concatenate([_project(rows[: stop - start], self.mean_docs,
                                      self.mean_x, self.w)
                             for start, stop, rows in corpus.blocks()])
        self.lo, hi = jnp.min(z, axis=0), jnp.max(z, axis=0)
        self.codes, self.scale = _quantize(z, self.lo, hi, levels=levels)
        del z
        self.mean_queries = jnp.mean(jnp.asarray(sample, jnp.float32),
                                     axis=0)
        self.levels = levels
        self.block_rows = block_rows
        self.n_docs = n

    def encode(self, queries: np.ndarray) -> jax.Array:
        y = _normalize(jnp.asarray(queries, jnp.float32) - self.mean_queries)
        return jnp.matmul(y - self.mean_x, self.w, precision=HIGHEST)

    def scores_of(self, queries: np.ndarray, ids: np.ndarray) -> np.ndarray:
        """Reference score of each (query, id); ids outside the corpus get
        NaN."""
        ids = np.asarray(ids)
        out = np.asarray(_scores_of(self.encode(queries), self.codes,
                                    self.scale, self.lo, jnp.asarray(ids)))
        return np.where((ids >= 0) & (ids < self.n_docs), out, np.nan)

    def topk(self, queries: np.ndarray, k: int
             ) -> tuple[np.ndarray, np.ndarray]:
        """Exact top-k (scores descending, lower id first among ties)."""
        zq = self.encode(queries)
        vals, ids = [], []
        for s in range(0, self.n_docs, self.block_rows):
            v, i = _block_topk(zq, self.codes[s: s + self.block_rows],
                               self.scale, self.lo, jnp.int32(s), k=k)
            vals.append(v)
            ids.append(i)
        v, pos = jax.lax.top_k(jnp.concatenate(vals, axis=1), k)
        i = jnp.take_along_axis(jnp.concatenate(ids, axis=1), pos, axis=1)
        return np.asarray(v), np.asarray(i)
