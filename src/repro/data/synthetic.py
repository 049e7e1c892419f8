"""Synthetic DPR-like knowledge base (offline stand-in for HotpotQA/NQ).

Real DPR-CLS embeddings are not downloadable in this environment, so we
synthesise a KB with the *measured statistics the paper reports* and the
structural properties that drive its findings:

* 768-dim fp32, **non-centered**: documents carry a large population mean
  offset and multiplicative norm jitter (paper Table 1: doc L2 12.3±0.6,
  query L2 9.3±0.2; queries are "more centered" than documents — exactly why
  uncentered PCA fitted on queries beats docs in Fig. 4, and why raw L2
  retrieval collapses while raw IP survives, Fig. 1).
* **Low effective rank + anisotropy**: the discriminative signal lives in an
  ``r_eff``-dim subspace with power-law spectrum plus a few dominating
  "rogue" dimensions (Timkey & van Schijndel 2021); the remaining dimensions
  are isotropic noise.  This is the structure PCA exploits (Fig. 4 plateau at
  ~128 dims) and what random projections destroy (Fig. 3).
* **Multi-hop relevance**: each query has r=2 relevant documents from two
  "articles" (HotpotQA's two supporting passages); the query embedding lies
  between its two article latents plus noise.

Everything is deterministic in ``seed``.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np
import jax.numpy as jnp

from repro.utils import chunked


@dataclasses.dataclass
class KBData:
    docs: jnp.ndarray        # (n_docs, d) fp32
    queries: jnp.ndarray     # (n_queries, d) fp32
    relevant: np.ndarray     # (n_queries, max_r) int32 doc ids, −1 pad
    meta: dict

    @property
    def dim(self) -> int:
        return int(self.docs.shape[-1])


_ROW_BLOCK = 65536


@functools.lru_cache(maxsize=8)
def _cached_kb(n_queries, n_docs, d, seed, r_eff, alpha, query_noise,
               doc_noise, doc_mean_norm, query_mean_norm, norm_jitter,
               beta_sigma, style_scale, mean_in_signal, spans_per_article):
    rng = np.random.default_rng(seed)

    # --- signal basis: r_eff orthonormal directions, power-law scaled,
    #     with 4 "rogue" high-variance dims mixed in.
    q_full, _ = np.linalg.qr(rng.standard_normal((d, d)).astype(np.float32))
    basis = q_full[:, :r_eff]                                   # (d, r_eff)
    spectrum = np.arange(1, r_eff + 1, dtype=np.float32) ** (-alpha / 2)
    spectrum /= np.sqrt(np.mean(spectrum ** 2))
    rogue = rng.choice(r_eff, size=4, replace=False)
    spectrum[rogue] *= 3.0

    def latent_to_obs(z):                                        # (n, r_eff)
        return (z * spectrum[None, :]) @ basis.T                 # (n, d)

    # --- population means.  A large fraction of the document offset lies
    #     *inside* the signal subspace: per-document norms then vary through
    #     the 2·μ·sig cross-term, which (a) breaks raw-L2 retrieval and
    #     normalize-without-center (Fig. 1 / Table 5) while leaving raw-IP
    #     rankings intact (q·μ is constant per query), and (b) is removed
    #     exactly by centering — reproducing the paper's core preprocessing
    #     finding.  Queries get a smaller, mostly-orthogonal offset
    #     ("queries are more centered", Table 1).
    mu_dir_in = latent_to_obs(rng.standard_normal((1, r_eff))
                              .astype(np.float32))[0]
    mu_dir_in /= np.linalg.norm(mu_dir_in)
    mu_docs = doc_mean_norm * (mean_in_signal * mu_dir_in
                               + np.sqrt(1 - mean_in_signal ** 2)
                               * q_full[:, r_eff])
    # Query offset partially aligned with the doc offset: the constant
    # q·μ_docs term is then large, and dividing it by per-document norms
    # (normalize WITHOUT centering) injects ranking noise — the paper's
    # "normalization alone sometimes hurts" effect (Table 5: 0.463 < 0.609).
    mu_queries = query_mean_norm * (
        0.7 * mu_docs / np.linalg.norm(mu_docs)
        + np.sqrt(1 - 0.7 ** 2) * q_full[:, r_eff + 1])

    # --- article latents with *tight* norm spread (DPR: 12.3 ± 0.6 — ±5%).
    #     Uniform norms kill "hub" articles, which is what keeps raw-IP
    #     retrieval nearly as good as center+norm (0.609 vs 0.618, Table 5).
    n_articles = max(2, n_docs // spans_per_article)
    z_art = rng.standard_normal((n_articles, r_eff)).astype(np.float32)
    sig = latent_to_obs(z_art)
    sig_norms = np.linalg.norm(sig, axis=1, keepdims=True)
    # in place, same operations in the same order as
    # ``sig / sig_norms * 8.0 * jitter``: at 2.1M docs each temporary is
    # another 6.45 GB of host memory
    sig /= sig_norms
    sig *= 8.0
    sig *= np.exp(rng.normal(0, 0.05, size=(n_articles, 1))).astype(np.float32)

    # --- documents: article signal + isotropic span noise + mean offset +
    #     "style" components.  Style dims are orthogonal to everything a
    #     query can contain: they leave inner products with queries intact
    #     but inject per-document norm variance — precisely the mechanism
    #     that collapses raw-L2 retrieval while raw-IP survives (Fig. 1 /
    #     Table 5: DPR-CLS IP 0.609 vs L2 0.240).
    art_of_doc = np.repeat(np.arange(n_articles), spans_per_article)[:n_docs]
    # drawn in row blocks: the same stream as one (n_docs, d) draw, without
    # its float64 intermediate
    eps_d = np.empty((n_docs, d), np.float32)
    for s, e in chunked(n_docs, _ROW_BLOCK):
        eps_d[s:e] = rng.standard_normal((e - s, d)).astype(np.float32) \
            * doc_noise
    n_style = 8
    style_basis = q_full[:, r_eff + 2: r_eff + 2 + n_style]      # (d, 8)
    h = rng.standard_normal((n_docs, n_style)).astype(np.float32) \
        * (style_scale / np.sqrt(n_style))
    s_i = np.exp(rng.normal(0.0, norm_jitter, size=(n_docs, 1))
                 ).astype(np.float32)
    # mu_docs is float64, so each document is summed in float64 and kept as
    # the float32 that KBData holds; block by block, the corpus never
    # exists in float64 at once
    h_style = h @ style_basis.T
    docs = np.empty((n_docs, d), np.float32)
    doc_l2, doc_l1 = [], []
    for s, e in chunked(n_docs, _ROW_BLOCK):
        block = mu_docs[None, :] + s_i[s:e] * sig[art_of_doc[s:e]] \
            + h_style[s:e] + eps_d[s:e]
        doc_l2.append(np.linalg.norm(block, axis=1))
        doc_l1.append(np.sum(np.abs(block), axis=1))
        docs[s:e] = block
    del eps_d, h_style

    # --- queries: midpoint of two articles + in-subspace noise, with a
    #     per-query signal strength β (heavy-tailed query difficulty — what
    #     makes compressed performance degrade *gradually*, as in Table 2,
    #     instead of cliff-dropping).
    a1 = rng.integers(0, n_articles, size=n_queries)
    a2 = (a1 + 1 + rng.integers(0, n_articles - 1, size=n_queries)) \
        % n_articles
    beta = np.exp(rng.normal(0.0, beta_sigma, size=(n_queries, 1))
                  ).astype(np.float32)
    eps_q = latent_to_obs(
        rng.standard_normal((n_queries, r_eff)).astype(np.float32))
    eps_q *= query_noise * 8.0 / np.sqrt(np.mean(np.sum(eps_q ** 2, -1)))
    queries = (mu_queries[None, :]
               + beta * 0.55 * (sig[a1] + sig[a2]) + eps_q)

    first_span = np.arange(n_articles) * spans_per_article
    rel = np.stack([first_span[a1], first_span[a2]], axis=1)
    rel = np.minimum(rel, n_docs - 1).astype(np.int32)

    meta = {
        "doc_l2": float(np.mean(np.concatenate(doc_l2))),
        "query_l2": float(np.mean(np.linalg.norm(queries, axis=1))),
        "doc_l1": float(np.mean(np.concatenate(doc_l1))),
        "query_l1": float(np.mean(np.sum(np.abs(queries), axis=1))),
        "seed": seed, "r_eff": r_eff, "alpha": alpha,
    }
    return docs, queries, rel, meta


def make_dpr_like_kb(n_queries: int = 2000, n_docs: int = 50_000,
                     d: int = 768, seed: int = 0, r_eff: int = 144,
                     alpha: float = 0.5, query_noise: float = 0.55,
                     doc_noise: float = 0.15, doc_mean_norm: float = 8.0,
                     query_mean_norm: float = 3.0, norm_jitter: float = 0.08,
                     beta_sigma: float = 0.8, style_scale: float = 6.0,
                     mean_in_signal: float = 0.6,
                     spans_per_article: int = 1) -> KBData:
    docs, queries, rel, meta = _cached_kb(
        n_queries, n_docs, d, seed, r_eff, alpha, query_noise, doc_noise,
        doc_mean_norm, query_mean_norm, norm_jitter, beta_sigma, style_scale,
        mean_in_signal, spans_per_article)
    return KBData(docs=jnp.asarray(docs), queries=jnp.asarray(queries),
                  relevant=rel, meta=meta)


def add_distractors(kb: KBData, n_extra: int, seed: int = 1) -> KBData:
    """Append irrelevant documents drawn from the same marginal (Fig. 6)."""
    rng = np.random.default_rng(seed)
    docs = np.asarray(kb.docs)
    i = rng.integers(0, docs.shape[0], size=n_extra)
    j = rng.integers(0, docs.shape[0], size=n_extra)
    w = rng.uniform(0.3, 0.7, size=(n_extra, 1)).astype(np.float32)
    extra = w * docs[i] + (1 - w) * docs[j] \
        + 0.3 * rng.standard_normal((n_extra, docs.shape[1])).astype(np.float32)
    new_docs = np.concatenate([docs, extra], axis=0)
    return KBData(docs=jnp.asarray(new_docs), queries=kb.queries,
                  relevant=kb.relevant,
                  meta={**kb.meta, "n_distractors": n_extra})
