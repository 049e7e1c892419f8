"""The device corpus generator at a small size on the CPU."""

import numpy as np
import pytest

from bench.corpus import Corpus, CorpusSpec, seed_key

N = 12_000


@pytest.fixture(scope="module")
def corpus():
    c = Corpus(CorpusSpec(n_docs=N), seed=2 ** 33 + 5)
    return c, c.host_docs(), c.queries(0, 1000)


def test_same_seed_same_corpus(corpus):
    c, docs, (q, rel) = corpus
    again = Corpus(CorpusSpec(n_docs=N), seed=2 ** 33 + 5)
    np.testing.assert_array_equal(again.host_docs(), docs)
    q2, rel2 = again.queries(0, 1000)
    np.testing.assert_array_equal(q2, q)
    np.testing.assert_array_equal(rel2, rel)


def test_another_seed_another_corpus(corpus):
    _, docs, _ = corpus
    other = Corpus(CorpusSpec(n_docs=N), seed=5)
    assert not np.array_equal(other.host_docs()[:100], docs[:100])


def test_seeds_above_32_bits_are_kept():
    import jax
    assert not np.array_equal(jax.random.key_data(seed_key(2 ** 33 + 7)),
                              jax.random.key_data(seed_key(7)))


def test_norms_match_the_host_generator(corpus):
    """make_dpr_like_kb's statistics (its defaults give doc L2 ≈ 13.5 and
    query L2 ≈ 10.6; the paper's Table 1 has 12.3 and 9.3)."""
    from repro.data import make_dpr_like_kb
    _, docs, (q, _) = corpus
    kb = make_dpr_like_kb(n_queries=1000, n_docs=N, seed=3)
    doc_l2 = np.linalg.norm(docs, axis=1).mean()
    q_l2 = np.linalg.norm(q, axis=1).mean()
    assert doc_l2 == pytest.approx(kb.meta["doc_l2"], rel=0.03)
    # the query norm follows the deployment's draw of the rogue directions
    # and mean offsets, which moves it by several percent between seeds of
    # the host generator too
    assert q_l2 == pytest.approx(kb.meta["query_l2"], rel=0.08)
    assert 12.0 < doc_l2 < 15.0 and 9.0 < q_l2 < 12.5


def test_two_distinct_supporting_passages_that_raw_ip_finds(corpus):
    _, docs, (q, rel) = corpus
    assert rel.shape == (1000, 2)
    assert (rel[:, 0] != rel[:, 1]).all()
    assert ((rel >= 0) & (rel < N)).all()
    top = np.argsort(-(q[:200] @ docs.T), axis=1)[:, :10]
    found = np.mean([len(set(t) & set(r)) / 2 for t, r in zip(top, rel[:200])])
    assert found > 0.5
