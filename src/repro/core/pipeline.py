"""Composable compression pipelines (the paper's full recipe as one object).

A :class:`CompressionPipeline` is an ordered list of transforms, e.g. the
paper's best 24× configuration::

    pipe = CompressionPipeline([
        CenterNorm(),                 # pre-processing  (§3.3)
        PCA(128, scale_components="paper"),
        CenterNorm(),                 # post-processing (§6)
        Int8Quantizer(),              # precision reduction (§4.4)
    ])
    pipe.fit(doc_embs, query_embs)
    docs_c  = pipe.transform(doc_embs, "docs")
    query_c = pipe.transform(q, "queries")

``fit`` threads the data through each stage as it fits (a stage sees the
output of its predecessors — matching the paper, where e.g. PCA is fitted on
already centered+normalized vectors).  Pipelines serialize to a flat dict of
arrays (``state_dict``/``load_state_dict``) for checkpointing, and report
their storage compression ratio.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import numpy as np

from repro.core import quantization as quant
from repro.core.preprocess import Transform
from repro.utils import chunked_map


class CompressionPipeline:
    def __init__(self, transforms: Sequence[Transform]):
        self.transforms = list(transforms)

    # -- fitting -------------------------------------------------------------
    def fit(self, docs: jax.Array, queries: Optional[jax.Array] = None,
            rng: Optional[jax.Array] = None) -> "CompressionPipeline":
        if rng is None:
            rng = jax.random.PRNGKey(0)
        for t in self.transforms:
            rng, sub = jax.random.split(rng)
            t.fit(docs, queries, rng=sub)
            # row chunks: a whole-corpus stage would hold its temporaries
            # for every row at once
            docs = chunked_map(functools.partial(t, kind="docs"), docs)
            if queries is not None:
                queries = t(queries, "queries")
        return self

    def fit_transform(self, docs, queries=None, rng=None):
        """Fit, then return (docs', queries') transformed by the full chain."""
        self.fit(docs, queries, rng)
        docs_t = self.transform(docs, "docs")
        queries_t = (self.transform(queries, "queries")
                     if queries is not None else None)
        return docs_t, queries_t

    # -- application -----------------------------------------------------------
    def transform(self, x: jax.Array, kind: str = "docs") -> jax.Array:
        for t in self.transforms:
            x = t(x, kind)
        return x

    def __call__(self, x, kind="docs"):
        return self.transform(x, kind)

    # -- storage accounting ------------------------------------------------------
    def compression_ratio(self, input_dim: int) -> float:
        return quant.compression_ratio(input_dim, self.transforms)

    def output_dim(self, input_dim: int) -> int:
        for t in self.transforms:
            input_dim = t.output_dim(input_dim)
        return input_dim

    # -- serialization -----------------------------------------------------------
    def state_dict(self) -> dict:
        return {"stages": [t.state_dict() for t in self.transforms],
                "types": [type(t).__name__ for t in self.transforms]}

    def load_state_dict(self, sd: dict) -> "CompressionPipeline":
        types = sd.get("types")
        if types is not None:
            have = [type(t).__name__ for t in self.transforms]
            if have != list(types):
                raise ValueError(
                    f"pipeline stage mismatch: state dict has {list(types)}, "
                    f"object has {have}")
        if len(sd["stages"]) != len(self.transforms):
            raise ValueError(
                f"pipeline length mismatch: state dict has "
                f"{len(sd['stages'])} stages, object has "
                f"{len(self.transforms)}")
        for t, stage_sd in zip(self.transforms, sd["stages"]):
            t.load_state(stage_sd)
        return self

    def save(self, path: str) -> None:
        flat: dict[str, np.ndarray] = {}
        for i, t in enumerate(self.transforms):
            for k, v in t.state.items():
                flat[f"{i}:{type(t).__name__}:{k}"] = np.asarray(v)
        np.savez(path, **flat)

    def load(self, path: str) -> "CompressionPipeline":
        """Load ``save`` output, routed through :meth:`load_state_dict`.

        Every stage goes through its own ``load_state`` so per-stage
        validation runs: a stateful stage whose keys are incomplete in the
        file raises instead of coming back half-fitted.  (Stages with no
        state in the file — Normalize, quantizer-style stateless transforms
        — are loaded as fitted with empty state, which their ``state_keys``
        check accepts only when they truly need none.)
        """
        data = np.load(path)
        per_stage: list[dict] = [{} for _ in self.transforms]
        for key in data.files:
            i_str, tname, k = key.split(":", 2)
            i = int(i_str)
            if not 0 <= i < len(self.transforms):
                raise ValueError(
                    f"pipeline file has stage index {i}, object has only "
                    f"{len(self.transforms)} stages")
            have = type(self.transforms[i]).__name__
            if have != tname:
                raise ValueError(
                    f"pipeline stage {i} mismatch: file has {tname}, "
                    f"object has {have}")
            per_stage[i][k] = data[key]
        sd = {"types": [type(t).__name__ for t in self.transforms],
              "stages": [{"name": t.name, "state": st, "fitted": True}
                         for t, st in zip(self.transforms, per_stage)]}
        return self.load_state_dict(sd)

    def __repr__(self) -> str:
        inner = ", ".join(type(t).__name__ for t in self.transforms)
        return f"CompressionPipeline([{inner}])"
