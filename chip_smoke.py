"""Serve the paper's compressed indexes on a TPU, end to end.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # sharded serving over four chips

One chip: a synthetic DPR-like corpus at the ``paper-dpr`` deployment's
widths (768-d, 2.1M passages; ``repro/configs/paper_dpr.py``) is compressed
into three indexes through the normal entry points — ``build_index`` →
``save_index`` → ``RetrievalService.register(artifact=...)`` — and a few
requests go through ``service.query(...).result()``:

* ``int8_exact``   PCA-128 + int8, exact search (the paper's 24× recipe);
* ``onebit_exact`` PCA-245 + 1-bit, exact search (the 100× recipe);
* ``int8_ivf``     PCA-128 + int8 behind 2048 inverted lists, 64 probed
  (k-means++ centroids, balanced lists), on the fused IVF kernel.

Each index must run a Pallas kernel (``tpu_custom_call`` in its compiled
search program) and agree with the same artifact served through the jnp
reference (``backend="jnp"``): the 1-bit index bit for bit (its arithmetic
is integer), the int8 ones to the tolerance of the kernels' bf16 MXU path.
Recall@10 against a float32 exact ranking of the uncompressed corpus,
storage bytes, the first request's seconds (compilation included) and the
request p50 are printed for the record; they are not benchmark metrics.

``--chips 4`` runs only the sharded phase: the exact int8 and IVF
artifacts are served both on one device and with ``ShardSpec(shards=4)``
behind the same service, and the sharded answers must be identical — ids
and float32 score bytes — with every shard on its own chip.

The last line of standard output is ``{"ok": true, "device": {...}}``.
Without a TPU the script exits nonzero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import resource
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.paper_dpr import FULL as PAPER_DPR  # noqa: E402
from repro.data import make_dpr_like_kb  # noqa: E402
from repro.retrieval import (IVFIndex, IndexSpec, ShardSpec,  # noqa: E402
                             build_index, recall_at_k, save_index)
from repro.serve import RetrievalService  # noqa: E402
from repro.utils import configure_compilation_cache  # noqa: E402

ARTIFACTS = ROOT / "artifacts" / "chip_smoke"
K = 10
NLIST, NPROBE = 2048, 64
REQUESTS, ROWS = 8, 32          # traffic per index
N_SAMPLE = 1024                 # query sample the pipelines are fitted with
SEED = 0                        # corpus and queries
TIMEOUT_S = 900.0
SCORE_TOL = 1e-2                # tests/test_ivf_fused.py::test_fused_agrees_with_jnp_path
MIN_OVERLAP = 0.9


def index_specs() -> dict[str, IndexSpec]:
    """The served recipes.  ``post=False`` keeps the trailing quantizer: the
    default post-processing stores float, and then no quantized kernel runs."""
    int8 = IndexSpec(method="pca_int8", dim=PAPER_DPR.pca_dim, post=False)
    return {
        "int8_exact": int8,
        "onebit_exact": IndexSpec(method="pca_onebit",
                                  dim=PAPER_DPR.pca_dim_100x, post=False),
        "int8_ivf": IndexSpec(method="pca_int8", dim=PAPER_DPR.pca_dim,
                              post=False, ivf=(NLIST, NPROBE),
                              kmeans_init="++", balanced_lists=True),
    }


def require_tpu(chips: int):
    """The first device, or exit: this program never carries on on a CPU."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX platform is "
                 f"{devices[0].platform!r})")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"JAX sees {len(devices)}")
    return devices[0]


def log(label: str, msg: str) -> None:
    print(f"[{label}] {msg}", flush=True)


def make_corpus(n_docs: int, label: str):
    """(host docs, pipeline-fit query sample, traffic, f32 exact top-k)."""
    t0 = time.perf_counter()
    kb = make_dpr_like_kb(n_queries=N_SAMPLE + REQUESTS * ROWS,
                          n_docs=n_docs, d=PAPER_DPR.dim, seed=SEED)
    sample, traffic = kb.queries[:N_SAMPLE], kb.queries[N_SAMPLE:]
    want = exact_topk(traffic, kb.docs)
    # build from host memory: the stages move the corpus to the device in
    # row chunks, so no whole-corpus copy competes with the indexes for HBM
    docs = np.asarray(kb.docs)
    log(label, f"corpus: {n_docs} docs x {PAPER_DPR.dim} f32 "
               f"({docs.nbytes} B), seed {SEED}, "
               f"made in {time.perf_counter() - t0:.1f} s")
    return docs, sample, np.asarray(traffic), want


def exact_topk(queries: jax.Array, docs: jax.Array) -> np.ndarray:
    """Top-K ids of the uncompressed corpus: float32 inner products at
    full matmul precision, on the device."""
    @jax.jit
    def top(q, d):
        s = jnp.matmul(q, d.T, precision=jax.lax.Precision.HIGHEST)
        return jax.lax.top_k(s, K)[1]
    return np.concatenate([np.asarray(top(queries[s: s + 64], docs))
                           for s in range(0, queries.shape[0], 64)])


def build_artifact(name: str, spec: IndexSpec, docs, sample,
                   label: str) -> Path:
    t0 = time.perf_counter()
    index = build_index(spec, docs, sample)
    path = ARTIFACTS / f"{name}.npz"
    save_index(index, str(path))
    shape = ""
    if isinstance(index, IVFIndex):
        shape = (f", {index.nlist} lists x max_len "
                 f"{int(index.lists.shape[1])}")
    ratio = docs.nbytes / index.nbytes
    log(label, f"{name}: built {type(index).__name__} "
               f"(scorer {index.scorer.name}{shape}), storage "
               f"{index.nbytes} B = {ratio:.1f}x smaller than f32, "
               f"in {time.perf_counter() - t0:.1f} s")
    return path


def serve(service: RetrievalService, name: str, traffic: np.ndarray):
    """Send the traffic one request at a time; (scores, ids, seconds each)."""
    scores, ids, seconds = [], [], []
    for s in range(0, traffic.shape[0], ROWS):
        t0 = time.perf_counter()
        res = service.query(traffic[s: s + ROWS], index=name,
                            k=K).result(timeout=TIMEOUT_S)
        seconds.append(time.perf_counter() - t0)
        scores.append(res.scores)
        ids.append(res.ids)
    return np.concatenate(scores), np.concatenate(ids), seconds


def served_index(service: RetrievalService, name: str):
    return service._registry.get(name).live_version().engine.index


def search_program(index, rows: np.ndarray) -> str:
    """Optimised HLO of the jitted search the served index ran for ``rows``
    (its first search cached the function; this lowers it again)."""
    q = jnp.asarray(rows)
    params = index.scorer.params()
    if isinstance(index, IVFIndex):
        if not index._use_fused_kernel:
            return ""
        list_storage, list_ids = index._list_major_layout()
        lowered = index._search_fn.lower(q, index.centroids, list_storage,
                                         list_ids, params, k=K,
                                         nprobe=index.nprobe)
    else:
        lowered = index._search_fn.lower(q, index.storage, params, k=K)
    return lowered.compile().as_text()


def timing(seconds: list[float]) -> str:
    return (f"first request {seconds[0]:.2f} s (compile included), "
            f"p50 {1e3 * float(np.median(seconds[1:])):.2f} ms over "
            f"{len(seconds) - 1} requests of {ROWS} rows")


def check_one_chip(service, name, path, traffic, want, label) -> list[str]:
    """Serve one artifact on the kernels and on the jnp reference."""
    t0 = time.perf_counter()
    service.register(name, artifact=str(path))
    service.register(f"{name}.jnp", artifact=str(path), backend="jnp")
    log(label, f"{name}: cold start of both versions from "
               f"{path.stat().st_size} B in {time.perf_counter() - t0:.1f} s")
    s_chip, i_chip, sec = serve(service, name, traffic)
    s_ref, i_ref, sec_ref = serve(service, f"{name}.jnp", traffic)
    failures = []

    kernel = "tpu_custom_call" in search_program(served_index(service, name),
                                                 traffic[:ROWS])
    if not kernel:
        failures.append(f"{name}: no Pallas kernel in the search program")

    delta = float(np.max(np.abs(s_chip - s_ref)))
    overlap = recall_at_k(i_chip, i_ref)
    if name == "onebit_exact":       # integer arithmetic: bit for bit
        same = (np.array_equal(i_chip, i_ref)
                and s_chip.tobytes() == s_ref.tobytes())
        parity = f"ids and scores identical: {same}"
        if not same:
            failures.append(f"{name}: differs from the jnp reference")
    else:
        close = bool(np.allclose(s_chip, s_ref, rtol=SCORE_TOL,
                                 atol=SCORE_TOL))
        parity = (f"max |score diff| {delta:.3g} (tol {SCORE_TOL}), "
                  f"top-{K} overlap {overlap:.4f}")
        if not close or overlap < MIN_OVERLAP:
            failures.append(f"{name}: {parity}")
    log(label, f"{name}: Pallas kernel in search program: {kernel}; "
               f"vs jnp reference: {parity}")
    log(label, f"{name}: recall@{K} vs f32 exact "
               f"{recall_at_k(i_chip, want):.4f} (jnp reference "
               f"{recall_at_k(i_ref, want):.4f}); {timing(sec)}; jnp "
               f"reference {timing(sec_ref)}")
    return failures


def placed_devices(index) -> set:
    """Devices holding rows of the index's mesh-sharded arrays."""
    devices = set()
    for value in vars(index).values():
        if isinstance(value, jax.Array) and \
                len(value.sharding.device_set) > 1:
            devices |= {s.device for s in value.addressable_shards
                        if s.data.size}
    return devices


def check_sharded(service, name, path, traffic, want, label) -> list[str]:
    """Serve one artifact on one device and over four shards."""
    sharded = f"{name}.shards4"
    t0 = time.perf_counter()
    service.register(name, artifact=str(path))
    service.register(sharded, artifact=str(path), shard=ShardSpec(shards=4))
    log(label, f"{name}: cold start single-device + 4 shards in "
               f"{time.perf_counter() - t0:.1f} s")
    s_one, i_one, sec_one = serve(service, name, traffic)
    s_four, i_four, sec_four = serve(service, sharded, traffic)
    devices = placed_devices(served_index(service, sharded))
    same = (np.array_equal(i_one, i_four)
            and s_one.tobytes() == s_four.tobytes())
    log(label, f"{name}: 4 shards on {len(devices)} devices "
               f"({sorted(d.id for d in devices)}); ids and f32 score "
               f"bytes identical to one device: {same}")
    log(label, f"{name}: recall@{K} vs f32 exact "
               f"{recall_at_k(i_four, want):.4f}; one device "
               f"{timing(sec_one)}; 4 shards {timing(sec_four)}")
    failures = []
    if len(devices) != 4:
        failures.append(f"{name}: shards placed on {len(devices)} devices")
    if not same:
        failures.append(f"{name}: sharded answers differ from one device")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded-serving phase, on four chips")
    ap.add_argument("--n-docs", type=int, default=PAPER_DPR.n_docs,
                    help="corpus size (default: the paper-dpr deployment)")
    args = ap.parse_args(argv)

    dev = require_tpu(args.chips)
    label = dev.device_kind
    log(label, f"{len(jax.devices())} x {dev.platform} {dev.device_kind}; "
               f"compile cache {configure_compilation_cache()}")
    if args.n_docs < PAPER_DPR.n_docs:
        log(label, f"n_docs cut: {args.n_docs} of the paper's "
                   f"{PAPER_DPR.n_docs}")

    specs = index_specs()
    names = (["int8_exact", "int8_ivf"] if args.chips == 4
             else list(specs))
    check = check_sharded if args.chips == 4 else check_one_chip
    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    failures = []
    try:
        docs, sample, traffic, want = make_corpus(args.n_docs, label)
        paths = {name: build_artifact(name, specs[name], docs, sample, label)
                 for name in names}
        del docs
        with RetrievalService(default_k=K) as service:
            for name in names:
                failures += check(service, name, paths[name], traffic, want,
                                  label)
    finally:
        shutil.rmtree(ARTIFACTS, ignore_errors=True)
    log(label, "host peak RSS "
               f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss} KiB")
    if failures:
        for f in failures:
            print(f"chip_smoke: FAILED {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
