"""A DPR-like passage corpus made on the device from a seed.

The structure is that of ``repro.data.synthetic.make_dpr_like_kb`` (the
paper's Table 1 setting: large, non-centered document norms, queries more
centered than documents, a low-rank power-law signal with four rogue
directions, a large document mean offset partly inside the signal subspace,
"style" dimensions orthogonal to everything a query holds, and two
supporting articles per query, one passage per article), drawn with
``jax.random`` on the device instead of host numpy.

The deployment's shape comes from the configuration's ``structure_seed``,
the rows from the run's seed.  Rows are drawn in fixed blocks of ``BLOCK`` rows, each from a key of its
own (``fold_in`` of the block's index), with the ``rbg`` generator (the
chip's hardware bit generator), so the corpus is made in seconds and is the
same on every host with the same device kind.  The block size is part of
the corpus's definition: the benchmark's reference regenerates the same
blocks from the same seed.  Nothing here imports the program.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
N_STYLE = 8
BLOCK = 65536          # document rows per key
QUERY_BLOCK = 1024     # query rows per key


@dataclasses.dataclass(frozen=True)
class CorpusSpec:
    """Sizes and statistics of one corpus (the ``corpus`` group of a
    configuration file); every passage is its own article.
    ``structure_seed`` fixes the deployment's shape (signal basis and
    spectrum, rogue directions, mean offsets): every run seed draws other
    rows of the same deployment."""

    n_docs: int
    structure_seed: int = 0
    d: int = 768
    r_eff: int = 144
    alpha: float = 0.5
    query_noise: float = 0.55
    doc_noise: float = 0.15
    doc_mean_norm: float = 8.0
    query_mean_norm: float = 3.0
    norm_jitter: float = 0.08
    beta_sigma: float = 0.8
    style_scale: float = 6.0
    mean_in_signal: float = 0.6

    @property
    def n_blocks(self) -> int:
        return -(-self.n_docs // BLOCK)


def seed_key(seed: int) -> jax.Array:
    """An ``rbg`` key from a seed of up to 64 bits (``jax.random.key``
    keeps only the low 32 bits of a larger one)."""
    if seed < 0:
        raise ValueError(f"seed must be ≥ 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF, impl="rbg")
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _latent_to_obs(z, spectrum, basis):
    return jnp.matmul(z * spectrum, basis.T, precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("spec",))
def _globals(key, spec: CorpusSpec) -> dict:
    d, r = spec.d, spec.r_eff
    k_basis, k_rogue, k_mu = jax.random.split(key, 3)
    q_full, _ = jnp.linalg.qr(jax.random.normal(k_basis, (d, d), jnp.float32))
    basis = q_full[:, :r]
    spectrum = jnp.arange(1, r + 1, dtype=jnp.float32) ** (-spec.alpha / 2)
    spectrum = spectrum / jnp.sqrt(jnp.mean(spectrum ** 2))
    rogue = jax.random.choice(k_rogue, r, (4,), replace=False)
    spectrum = spectrum.at[rogue].multiply(3.0)
    mu_in = _latent_to_obs(jax.random.normal(k_mu, (1, r)), spectrum, basis)[0]
    mu_in = mu_in / jnp.linalg.norm(mu_in)
    mis = spec.mean_in_signal
    mu_docs = spec.doc_mean_norm * (mis * mu_in
                                    + math.sqrt(1 - mis ** 2) * q_full[:, r])
    mu_queries = spec.query_mean_norm * (
        0.7 * mu_docs / jnp.linalg.norm(mu_docs)
        + math.sqrt(1 - 0.7 ** 2) * q_full[:, r + 1])
    return {"basis": basis, "spectrum": spectrum, "mu_docs": mu_docs,
            "mu_queries": mu_queries,
            "style": q_full[:, r + 2: r + 2 + N_STYLE]}


def _article_block(key, b, g, spec: CorpusSpec):
    """Signals of articles ``[b·BLOCK, (b+1)·BLOCK)``: unit directions of
    the latent signal, scaled to norm 8 · jitter."""
    k_z, k_j = jax.random.split(jax.random.fold_in(key, b))
    z = jax.random.normal(k_z, (BLOCK, spec.r_eff), jnp.float32)
    jitter = jnp.exp(0.05 * jax.random.normal(k_j, (BLOCK,), jnp.float32))
    sig = _latent_to_obs(z, g["spectrum"], g["basis"])
    return sig / jnp.linalg.norm(sig, axis=1, keepdims=True) \
        * (8.0 * jitter)[:, None]


@functools.partial(jax.jit, static_argnames=("spec",))
def _doc_block(keys, g, b, *, spec: CorpusSpec):
    sig = _article_block(keys["articles"], b, g, spec)
    k_eps, k_h, k_s = jax.random.split(jax.random.fold_in(keys["docs"], b), 3)
    eps = jax.random.normal(k_eps, (BLOCK, spec.d), jnp.float32) \
        * spec.doc_noise
    h = jax.random.normal(k_h, (BLOCK, N_STYLE), jnp.float32) \
        * (spec.style_scale / math.sqrt(N_STYLE))
    s = jnp.exp(spec.norm_jitter
                * jax.random.normal(k_s, (BLOCK,), jnp.float32))
    style = jnp.matmul(h, g["style"].T, precision=HIGHEST)
    return g["mu_docs"][None, :] + s[:, None] * sig + style + eps


@functools.partial(jax.jit, static_argnames=("spec",))
def _query_block(keys, g, qb, *, spec: CorpusSpec):
    n = spec.n_docs
    k_a1, k_a2, k_b, k_e = jax.random.split(
        jax.random.fold_in(keys["queries"], qb), 4)
    a1 = jax.random.randint(k_a1, (QUERY_BLOCK,), 0, n)
    a2 = (a1 + 1 + jax.random.randint(k_a2, (QUERY_BLOCK,), 0, n - 1)) % n
    beta = jnp.exp(spec.beta_sigma * jax.random.normal(k_b, (QUERY_BLOCK,)))
    z = jax.random.normal(k_e, (QUERY_BLOCK, spec.r_eff), jnp.float32)
    eps = _latent_to_obs(z, g["spectrum"], g["basis"])
    # the population RMS of eps is sqrt(sum(spectrum²)) (orthonormal basis),
    # so each row is scaled by that expectation, independent of the others
    eps = eps * (spec.query_noise * 8.0
                 / jnp.sqrt(jnp.sum(g["spectrum"] ** 2)))

    def gather(b, acc):
        sig = _article_block(keys["articles"], b, g, spec)
        for j, a in enumerate((a1, a2)):
            here = (a // BLOCK) == b
            acc = acc.at[j].set(jnp.where(here[:, None], sig[a % BLOCK],
                                          acc[j]))
        return acc
    sigs = jax.lax.fori_loop(0, spec.n_blocks, gather,
                             jnp.zeros((2, QUERY_BLOCK, spec.d), jnp.float32))
    q = g["mu_queries"][None, :] + (beta * 0.55)[:, None] * (sigs[0] + sigs[1]) \
        + eps
    return q, jnp.stack([a1, a2], axis=1).astype(jnp.int32)


class Corpus:
    """The corpus of one seed: documents by block, queries by index.

    Query indices ``[0, n_sample)`` are the sample the index pipeline is
    fitted with; the traffic's pool is drawn from indices after them, so no
    pool row was seen by the fit.
    """

    def __init__(self, spec: CorpusSpec, seed: int):
        self.spec = spec
        self.seed = int(seed)
        k_a, k_d, k_q = jax.random.split(seed_key(self.seed), 3)
        self._keys = {"articles": k_a, "docs": k_d, "queries": k_q}
        self._g = _globals(seed_key(spec.structure_seed), spec)

    def blocks(self):
        """Yield ``(start, stop, rows)`` over the corpus; ``rows`` is a
        device array of BLOCK rows, of which the first stop − start are
        the corpus's (the last block is cut on the host side)."""
        for b in range(self.spec.n_blocks):
            s = b * BLOCK
            yield s, min(s + BLOCK, self.spec.n_docs), _doc_block(
                self._keys, self._g, jnp.int32(b), spec=self.spec)

    def host_docs(self) -> np.ndarray:
        """The whole corpus in host memory, pulled block by block (the next
        block is made while one is copied), so the device never holds more
        than two blocks of it."""
        out = np.empty((self.spec.n_docs, self.spec.d), np.float32)
        it = self.blocks()
        ahead = next(it, None)
        while ahead is not None:
            s, e, rows = ahead
            rows.copy_to_host_async()
            ahead = next(it, None)
            out[s:e] = np.asarray(rows)[: e - s]
        return out

    def queries(self, start: int, n: int) -> tuple[np.ndarray, np.ndarray]:
        """Queries ``start … start + n − 1`` and their two supporting
        passages, (n, d) float32 and (n, 2) int32."""
        first, last = start // QUERY_BLOCK, (start + n - 1) // QUERY_BLOCK
        qs, rels = zip(*(_query_block(self._keys, self._g, jnp.int32(qb),
                                      spec=self.spec)
                         for qb in range(first, last + 1)))
        off = start - first * QUERY_BLOCK
        q = np.concatenate([np.asarray(x) for x in qs])[off: off + n]
        rel = np.concatenate([np.asarray(x) for x in rels])[off: off + n]
        return q, rel
