"""Scorer backends: one scoring code path from kernel to fleet.

A :class:`Scorer` owns the *storage representation* of an index (float /
fp16 / int8 codes / bit-packed words) and knows how to score float queries
against it through the matching kernel path (Pallas on TPU, jnp oracle
elsewhere).  :class:`~repro.retrieval.index.CompressedIndex`,
:class:`~repro.retrieval.sharded.ShardedCompressedIndex`, and
:mod:`repro.serve` all dispatch through the same scorer objects, so the
quantized kernels serve single-host, sharded, and streaming-request
workloads identically.

Design contract (everything shard_map / jit needs):

* ``encode_docs(x)`` / ``encode_queries(q)`` — storage resp. query-side
  representation.  ``x``/``q`` have already passed through the pipeline's
  *float* stages; the scorer handles only the final precision step.
* ``params()`` — the jnp arrays scoring depends on (quantizer codebooks).
  Passed explicitly through ``shard_map`` so nothing is closed over.
* ``scores(q, storage, params=None)`` — dense (Q, D) similarity.  Pure and
  traceable: safe to call under ``jit`` and inside ``shard_map`` on a
  storage *shard*.
* ``decode(storage)`` — float view of the storage (shadow scoring,
  fallback paths).

Scorers are selected from a pipeline's trailing quantizer via
:func:`scorer_for_pipeline` (or by name via :func:`get_scorer`).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.pipeline import CompressionPipeline
from repro.core.preprocess import Transform
from repro.core.quantization import FloatCast, Int8Quantizer, OneBitQuantizer
from repro.retrieval.topk import similarity
from repro.utils import chunked_map


def _resolve_pallas(backend: str) -> bool:
    """backend ∈ {"auto", "jnp", "pallas"} → use the Pallas kernel path?"""
    if backend == "pallas":
        return True
    if backend == "jnp":
        return False
    if backend == "auto":
        return jax.default_backend() == "tpu"
    raise ValueError(f"unknown backend {backend!r}")


class Scorer:
    """Base scorer: float storage, plain GEMM similarity."""

    name = "float"

    def __init__(self, sim: str = "ip", backend: str = "auto"):
        self.sim = sim
        self.backend = backend

    @property
    def use_pallas(self) -> bool:
        return _resolve_pallas(self.backend)

    # -- encoding ---------------------------------------------------------
    def encode_docs(self, x: jax.Array) -> jax.Array:
        return x

    def encode_queries(self, q: jax.Array) -> jax.Array:
        return q

    # -- scoring ----------------------------------------------------------
    def params(self) -> dict[str, jax.Array]:
        """Arrays ``scores`` reads — threaded through shard_map explicitly."""
        return {}

    def scores(self, q: jax.Array, storage: jax.Array,
               params: Optional[dict] = None) -> jax.Array:
        return similarity(q, storage, self.sim)

    def scores_gathered(self, q: jax.Array, gathered: jax.Array,
                        params: Optional[dict] = None) -> jax.Array:
        """Per-query candidate scoring: (Q, d) × (Q, C, w) → (Q, C).

        The IVF path gathers each query's probed inverted lists into its own
        candidate block; scoring vmaps the backend's regular ``scores``
        kernel over the query axis, so every storage format reuses the same
        kernel code for approximate search.  Pure and traceable, like
        ``scores``.
        """
        p = params if params is not None else self.params()

        def _one(qi, gi):
            return self.scores(qi[None, :], gi, params=p)[0]

        return jax.vmap(_one)(q, gathered)

    # -- persistence -------------------------------------------------------
    def extra_state(self) -> dict:
        """Scorer-owned scalars outside the quantizer's state (artifact
        format; codebooks live in the pipeline's stage state already)."""
        return {}

    def load_extra_state(self, sd: dict) -> None:
        pass

    # -- float view -------------------------------------------------------
    def decode(self, storage: jax.Array) -> jax.Array:
        return storage

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"{type(self).__name__}(sim={self.sim!r}, backend={self.backend!r})"


class FloatCastScorer(Scorer):
    """fp16/bf16 storage; scoring upcasts once (callers cache the view)."""

    name = "fp16"

    def __init__(self, quantizer: FloatCast, sim: str = "ip",
                 backend: str = "auto"):
        super().__init__(sim=sim, backend=backend)
        self.quantizer = quantizer

    def encode_docs(self, x):
        return self.quantizer.encode(x, "docs")

    def scores(self, q, storage, params=None):
        return similarity(q, self.quantizer.decode(storage), self.sim)

    def decode(self, storage):
        return self.quantizer.decode(storage)


class Int8Scorer(Scorer):
    """uint8 codes; affine decode folded into the int8 IP kernel."""

    name = "int8"

    def __init__(self, quantizer: Int8Quantizer, sim: str = "ip",
                 backend: str = "auto"):
        super().__init__(sim=sim, backend=backend)
        self.quantizer = quantizer

    def encode_docs(self, x):
        return self.quantizer.encode(x, "docs")

    def params(self):
        return {"scale": self.quantizer.state["scale"],
                "zero": self.quantizer.state["zero"]}

    def scores(self, q, storage, params=None):
        from repro.kernels.int8_ip import ops as int8_ops
        p = params if params is not None else self.params()
        return int8_ops.int8_scores(q, storage, scale=p["scale"],
                                    zero=p["zero"], sim=self.sim,
                                    use_pallas=self.use_pallas)

    def decode(self, storage):
        return self.quantizer.decode(storage)


class OneBitScorer(Scorer):
    """Bit-packed uint32 storage; sign-matmul kernel scoring.

    ``dim`` is the logical (unpadded) float dimensionality — needed because
    the packed words round it up to a multiple of 32.  It is recorded at
    ``encode_docs`` time and must be set before scoring raw storage.
    """

    name = "onebit"

    def __init__(self, quantizer: OneBitQuantizer, sim: str = "ip",
                 backend: str = "auto", dim: Optional[int] = None):
        super().__init__(sim=sim, backend=backend)
        self.quantizer = quantizer
        self.dim = dim

    def extra_state(self):
        return {"dim": self.dim}

    def load_extra_state(self, sd):
        if sd.get("dim") is not None:
            self.dim = int(sd["dim"])

    def encode_docs(self, x):
        self.dim = int(x.shape[-1])
        return self.quantizer.encode(x, "docs")

    def encode_queries(self, q):
        # offset-encoded floats: only signs reach the kernel, the offset
        # correction is applied analytically inside binary_ip_scores.
        return self.quantizer(q, "queries")

    def scores(self, q, storage, params=None):
        from repro.kernels.binary_ip import ops as binary_ops
        if self.dim is None:
            raise ValueError("OneBitScorer.dim unset — encode_docs first or "
                             "pass dim= at construction")
        return binary_ops.binary_ip_scores(
            q, storage, self.dim, offset=self.quantizer.offset,
            use_pallas=self.use_pallas)

    def decode(self, storage):
        return self.quantizer.decode(storage, self.dim)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

# quantizer class → scorer factory.  Extend with register_scorer().
_SCORER_FOR_QUANTIZER: dict[type, Callable[..., Scorer]] = {}
_SCORER_BY_NAME: dict[str, Callable[..., Scorer]] = {}


def register_scorer(name: str, quantizer_cls: Optional[type],
                    factory: Callable[..., Scorer]) -> None:
    """Register a scorer backend under ``name`` (and its quantizer class).

    ``factory(quantizer, sim=..., backend=...) → Scorer``; for quantizer-less
    backends (plain float) the quantizer argument is None.
    """
    _SCORER_BY_NAME[name] = factory
    if quantizer_cls is not None:
        _SCORER_FOR_QUANTIZER[quantizer_cls] = factory


register_scorer("float", None,
                lambda quantizer=None, **kw: Scorer(**kw))
register_scorer("fp16", FloatCast,
                lambda quantizer=None, **kw: FloatCastScorer(
                    quantizer or FloatCast(), **kw))
register_scorer("int8", Int8Quantizer,
                lambda quantizer=None, **kw: Int8Scorer(
                    quantizer or Int8Quantizer(), **kw))
register_scorer("onebit", OneBitQuantizer,
                lambda quantizer=None, **kw: OneBitScorer(
                    quantizer or OneBitQuantizer(), **kw))


def scorer_names() -> tuple[str, ...]:
    return tuple(_SCORER_BY_NAME)


def backend_tail_stages() -> dict[str, list[Transform]]:
    """Canonical {backend name: trailing pipeline stages} sweep table.

    One place for tests and benchmarks that cover every scorer backend;
    stages are stateful once fitted, so each call returns fresh instances
    (never share them across pipelines).
    """
    return {"float": [], "fp16": [FloatCast()],
            "int8": [Int8Quantizer()], "onebit": [OneBitQuantizer(0.5)]}


def get_scorer(name: str, quantizer: Optional[Transform] = None,
               sim: str = "ip", backend: str = "auto") -> Scorer:
    if name not in _SCORER_BY_NAME:
        raise KeyError(f"unknown scorer {name!r}; have {scorer_names()}")
    return _SCORER_BY_NAME[name](quantizer, sim=sim, backend=backend)


def apply_float_stages(stages, x: jax.Array, kind: str) -> jax.Array:
    """Run docs/queries through a pipeline's float stages (shared by the
    single-host index, the sharded index, and the shadow scorer — one
    definition so the three paths can never diverge).

    Rows go through in chunks: encoding a corpus then holds the stages'
    input-width temporaries for one chunk, not for every row (at 2.1M
    768-d docs each whole-corpus temporary is 6.45 GB of a 16 GB device).
    A host (numpy) corpus moves to the device chunk by chunk.
    """
    def run(rows):
        rows = jnp.asarray(rows)
        for t in stages:
            rows = t(rows, kind)
        return rows

    return chunked_map(run, x)


def _factory_for(quantizer: Transform) -> Optional[Callable[..., Scorer]]:
    factory = _SCORER_FOR_QUANTIZER.get(type(quantizer))
    if factory is not None:
        return factory
    for cls, factory in _SCORER_FOR_QUANTIZER.items():
        if isinstance(quantizer, cls):
            return factory
    return None


def split_pipeline(pipeline: CompressionPipeline
                   ) -> tuple[list[Transform], Optional[Transform]]:
    """Split transforms into (float stages, trailing quantizer|None)."""
    stages = list(pipeline.transforms)
    if stages and _factory_for(stages[-1]) is not None:
        return stages[:-1], stages[-1]
    return stages, None


def scorer_for_pipeline(pipeline: CompressionPipeline, sim: str = "ip",
                        backend: str = "auto"
                        ) -> tuple[list[Transform], Scorer]:
    """(float stages, scorer) for a pipeline's storage representation."""
    float_stages, quantizer = split_pipeline(pipeline)
    if quantizer is None:
        return float_stages, Scorer(sim=sim, backend=backend)
    return float_stages, _factory_for(quantizer)(quantizer, sim=sim,
                                                 backend=backend)
