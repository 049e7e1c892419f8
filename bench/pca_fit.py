"""The PCA fit that every reference recipe shares.

It computes, from the corpus alone, what the configuration states ahead of
the code step: center and L2-normalize each population, PCA to ``dim``
components fitted on the documents (moments summed block by block, then
``eigh``), and the projection of documents and of the float queries onto
those components, in straightforward ``jax.numpy`` at float32 and
``Precision.HIGHEST``.  It imports nothing of the program and reads nothing
the program made: no pipeline state, codebook, centroid or code.  The
documents are regenerated on the device from the seed, block by block, so
a recipe runs after the program's state has been freed.

The recipes under ``bench/references/`` take a :class:`PCAFit` and add the
code step and the score.
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


class PCAFit:
    """Center+normalize and PCA to ``dim`` components, fitted on the
    documents of ``corpus``.

    ``corpus`` yields ``(start, stop, rows)`` device blocks from
    :meth:`blocks` (rows past ``stop − start`` are not the corpus's);
    ``sample`` is the query sample the configuration's index is fitted
    with (its mean centers the queries).
    """

    def __init__(self, corpus, sample: np.ndarray, dim: int):
        self.corpus = corpus
        self.n_docs = n = corpus.spec.n_docs
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
        self.mean_queries = jnp.mean(jnp.asarray(sample, jnp.float32),
                                     axis=0)

    def doc_blocks(self):
        """Yield each block's documents projected onto the components,
        (stop − start, dim) float32, in corpus order."""
        for start, stop, rows in self.corpus.blocks():
            yield _project(rows[: stop - start], self.mean_docs, self.mean_x,
                           self.w)

    def encode(self, queries: np.ndarray) -> jax.Array:
        """The queries' float PCA coordinates, (n, dim) float32."""
        y = _normalize(jnp.asarray(queries, jnp.float32) - self.mean_queries)
        return jnp.matmul(y - self.mean_x, self.w, precision=HIGHEST)


def merged_topk(block_topk, n_docs: int, block_rows: int, k: int
                ) -> tuple[np.ndarray, np.ndarray]:
    """Exact top-k over the corpus from ``block_topk(start, stop)``, each
    block's own ``(scores, ids)`` top k: scores descending, the lower id
    first among ties (``lax.top_k`` keeps the lower position first, and the
    blocks are concatenated in id order)."""
    vals, ids = [], []
    for s in range(0, n_docs, block_rows):
        v, i = block_topk(s, s + block_rows)
        vals.append(v)
        ids.append(i)
    v, pos = jax.lax.top_k(jnp.concatenate(vals, axis=1), k)
    i = jnp.take_along_axis(jnp.concatenate(ids, axis=1), pos, axis=1)
    return np.asarray(v), np.asarray(i)


def scores_or_nan(scores, ids: np.ndarray, n_docs: int) -> np.ndarray:
    """``scores`` with NaN where an id lies outside the corpus."""
    return np.where((ids >= 0) & (ids < n_docs), np.asarray(scores), np.nan)
