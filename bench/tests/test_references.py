"""Reference recipes are found by name, and ``pca_sign`` holds the program's
1-bit index to the paper's 100× recipe, at 4,096 passages on the CPU."""

import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bench import catalog, check
from bench.corpus import Corpus, CorpusSpec

REPO = Path(__file__).resolve().parents[2]
N_DOCS = 4096
SEED = 7
K = 10
# Sign flips allowed per (query, passage) pair, each worth ½ of a score.
# Components past the corpus's signal rank lie in a near-isotropic noise
# subspace, so a coordinate near 0 can take the other bit in the program
# than in the reference.  Nine seeds here read at most one flip a pair
# (score_err) and two across the pairs that rank_gap compares.
M = 3
# The program's served 1-bit scores hold the pad bits: ``binary_ip_scores``
# (kernels/binary_ip/ops.py) scores all 256 packed bits, whose 11 pad bits
# are −1 on both sides, so each adds ¼.  A defect: the repair makes the
# offset at 245 dimensions 0.
PAD_OFFSET = {245: 2.75, 256: 0.0}


def _corpus_group():
    path = REPO / "bench" / "configs" / "dpr2m-pca128-int8.json"
    return json.loads(path.read_text())["corpus"]


def test_every_configuration_names_a_recipe_and_the_int8_ones_pca_affine():
    bench = catalog.load_benchmark(REPO)
    for entry in bench["configs"]:
        config = json.loads((REPO / entry["file"]).read_text())
        name = config["reference"]["recipe"]
        recipe = catalog.reference_recipe(name, REPO)
        assert callable(recipe.build) and callable(recipe.control)
        if entry["name"] in ("dpr2m-pca128-int8", "dpr2m-pca128-int8-ivf200"):
            assert name == "pca_affine"


@pytest.mark.parametrize("name", [None, "", "pca_nope"])
def test_a_missing_or_unknown_recipe_is_an_error_naming_the_files(name):
    with pytest.raises(FileNotFoundError) as err:
        catalog.reference_recipe(name, REPO)
    assert "pca_affine.py" in str(err.value)
    assert "pca_sign.py" in str(err.value)


def test_pca_affine_has_no_control_below_an_unknown_step():
    recipe = catalog.reference_recipe("pca_affine", REPO)
    with pytest.raises(ValueError, match="levels=7"):
        recipe.control(None, None, dim=16, levels=7)


class _Identity:
    """A fit whose query coordinates are the queries themselves."""

    def __init__(self, n_docs):
        self.n_docs = n_docs

    def encode(self, queries):
        return jnp.asarray(queries, jnp.float32)


def test_pca_sign_is_the_plain_sign_inner_product():
    rng = np.random.default_rng(2 ** 32 + 5)
    zq = rng.standard_normal((64, 245)).astype(np.float32)
    zd = rng.standard_normal((512, 245)).astype(np.float32)
    zq[::5, ::3] = 0.0                      # zero takes the bit 1
    zd[::7, ::5] = 0.0
    want = (((zq >= 0) - 0.5) @ ((zd >= 0) - 0.5).T)  # float64, exact
    codes = jnp.asarray(np.where(zd >= 0, 1, -1).astype(np.int8))
    sign = catalog.reference_recipe("pca_sign", REPO)
    ref = sign.SignSearch(_Identity(512), codes, 0.5, block_rows=128)

    every = np.broadcast_to(np.arange(512), (64, 512))
    np.testing.assert_array_equal(ref.scores_of(zq, every), want)
    order = np.argsort(-want, axis=1, kind="stable")[:, :K]
    scores, ids = ref.topk(zq, K)
    np.testing.assert_array_equal(ids, order)
    np.testing.assert_array_equal(scores,
                                  np.take_along_axis(want, order, axis=1))
    outside = ref.scores_of(zq[:2], np.array([[-1, 512], [0, 3]]))
    assert np.isnan(outside[0]).all() and np.isfinite(outside[1]).all()


@pytest.fixture(scope="module", params=[245, 256])
def onebit(request):
    """The program's PCA + 1-bit index of a small corpus, searched through
    ``CompressedIndex.search``, and the ``pca_sign`` recipe of the same
    corpus; ``dim`` 256 packs without pad bits."""
    from repro.retrieval import IndexSpec, build_index
    dim = request.param
    corpus = Corpus(CorpusSpec(**{**_corpus_group(), "n_docs": N_DOCS}),
                    SEED)
    fit_q, _ = corpus.queries(0, 1024)
    pool, _ = corpus.queries(1024, 64)
    index = build_index(IndexSpec(method="pca_onebit", dim=dim, post=False),
                        corpus.host_docs(), fit_q)
    assert type(index).__name__ == "CompressedIndex"
    scores, ids = index.search(jnp.asarray(pool), K)
    sign = catalog.reference_recipe("pca_sign", REPO)
    return {"dim": dim, "corpus": corpus, "fit_q": fit_q, "pool": pool,
            "scores": np.asarray(scores), "ids": np.asarray(ids),
            "sign": sign, "ref": sign.build(corpus, fit_q, dim=dim,
                                            offset=0.5)}


def _limits(dim):
    return {"score_err": PAD_OFFSET[dim] + 0.5 * M, "rank_gap": 0.5 * M}


def test_pca_sign_holds_the_programs_onebit_index(onebit):
    dim, ref, pool = onebit["dim"], onebit["ref"], onebit["pool"]
    served, ids = onebit["scores"], onebit["ids"]
    gap = served - ref.scores_of(pool, ids)
    assert np.median(gap) == PAD_OFFSET[dim]          # the defect above
    assert np.max(np.abs(gap - PAD_OFFSET[dim])) <= 0.5 * M
    assert np.all(gap * 4 == np.round(gap * 4))       # whole quarters
    values = check.compare(pool, ids, served, ref, K, _limits(dim))
    assert values["score_err"] - PAD_OFFSET[dim] <= 0.5 * M, values
    assert values["rank_gap"] <= 0.5 * M, values
    assert check.verdict(values, _limits(dim)), values


def test_the_pca_sign_control_is_not_correct(onebit):
    """The recipe over half the components, in the program's place, fails
    the limits that the program's answers keep, and reads at least three
    times the program's ``rank_gap``."""
    dim, ref, pool = onebit["dim"], onebit["ref"], onebit["pool"]
    program = check.compare(pool, onebit["ids"], onebit["scores"], ref, K,
                            _limits(dim))
    control = onebit["sign"].control(onebit["corpus"], onebit["fit_q"],
                                     dim=dim, offset=0.5)
    scores, ids = control.topk(pool, K)
    values = check.compare(pool, ids, scores, ref, K, _limits(dim))
    assert not check.verdict(values, _limits(dim)), values
    assert values["rank_gap"] >= 3 * max(program["rank_gap"], 0.5), values
