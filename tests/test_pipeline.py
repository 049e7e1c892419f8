import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (METHODS, CenterNorm, CompressionPipeline,
                        Int8Quantizer, PCA, build_method,
                        method_compression_ratio)
from repro.data import make_dpr_like_kb


@pytest.fixture(scope="module")
def kb():
    return make_dpr_like_kb(n_queries=64, n_docs=2000, d=128, r_eff=48)


def test_fit_threads_through_stages(kb):
    """Each stage must be fitted on its predecessors' output."""
    pipe = CompressionPipeline([CenterNorm(), PCA(16), CenterNorm()])
    pipe.fit(kb.docs, kb.queries)
    # the PCA mean must be ~0-mean data (post CenterNorm), i.e. small
    assert float(jnp.linalg.norm(pipe.transforms[1].state["mean"])) < 0.5


def test_fit_transform(kb):
    pipe = CompressionPipeline([CenterNorm(), PCA(16)])
    d, q = pipe.fit_transform(kb.docs, kb.queries)
    assert d.shape == (2000, 16) and q.shape == (64, 16)


def test_save_load_roundtrip(tmp_path, kb):
    pipe = CompressionPipeline([CenterNorm(), PCA(16), CenterNorm(),
                                Int8Quantizer()])
    pipe.fit(kb.docs, kb.queries)
    path = str(tmp_path / "pipe.npz")
    pipe.save(path)
    pipe2 = CompressionPipeline([CenterNorm(), PCA(16), CenterNorm(),
                                 Int8Quantizer()]).load(path)
    np.testing.assert_allclose(np.asarray(pipe.transform(kb.docs)),
                               np.asarray(pipe2.transform(kb.docs)),
                               rtol=1e-6)


def test_registry_builds_every_method(kb):
    cheap = [m for m in METHODS
             if m not in ("greedy_dim_drop", "distance_learning",
                          "contrastive") and not m.startswith("ae_")]
    for name in cheap:
        pipe = build_method(name, dim=16)
        d, q = pipe.fit_transform(kb.docs, kb.queries)
        assert d.shape[0] == 2000
        assert not bool(jnp.any(jnp.isnan(jnp.asarray(d, jnp.float32))))


def test_method_ratios():
    assert method_compression_ratio("pca", 128) == pytest.approx(6.0)
    assert method_compression_ratio("pca_int8", 128) == pytest.approx(24.0)
    assert method_compression_ratio("onebit", 128) == pytest.approx(32.0)
    assert method_compression_ratio(
        "pca_onebit", 245) == pytest.approx(100.0, rel=0.01)


@pytest.mark.parametrize("chunk", [16, 70, 500])
def test_chunked_map_matches_whole_array(kb, chunk):
    """Chunked application (how a corpus too large for one pass goes
    through a stage) is the whole-array result, chunk size notwithstanding,
    from host or device input."""
    from repro.utils import chunked_map
    pipe = CompressionPipeline([CenterNorm(), PCA(16), Int8Quantizer()])
    pipe.fit(kb.docs[:500], kb.queries)
    docs = np.asarray(kb.docs[:500])
    stage = pipe.transforms[0]
    whole = stage(jnp.asarray(docs), "docs")
    for x in (docs, jnp.asarray(docs)):
        out = chunked_map(lambda rows: stage(rows, "docs"), x, chunk)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(whole))
    codes = pipe.transforms[2].encode(pipe.transforms[1](whole), "docs")
    out = chunked_map(lambda rows: pipe.transforms[2].encode(
        pipe.transforms[1](rows), "docs"), whole, chunk)
    assert out.dtype == codes.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(out), np.asarray(codes))
