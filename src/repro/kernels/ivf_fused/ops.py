"""Public op: fused IVF top-k over probed lists, any scorer backend.

Handles the backend-specific query-side encoding (the document side is the
list-major storage prepared once by :class:`repro.retrieval.ivf.IVFIndex`)
and dispatches to the Pallas kernel (interpret mode off-TPU) or the jnp
reference mirror.  Score corrections that are affine in the query — int8's
``q·zero`` dequant term, residual encoding's routed ``q·centroid`` term —
are folded into the per-(query, probe) ``base`` matrix, which the kernel
scatters into its dense (Q, nlist) correction matrix: one column add per
scored tile applies them and masks the rows that did not probe the list.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.ivf_fused import ref as _ref
from repro.kernels.ivf_fused.kernel import fused_ivf_topk_pallas


def prepare_queries(q: jax.Array, backend: str, params: dict, *,
                    packed_width: Optional[int] = None
                    ) -> tuple[jax.Array, jax.Array]:
    """Float queries (post float-stages) → (qe, base_q) for the kernel.

    ``base_q`` (Q,) is the query-only additive score term (0 except int8's
    ``q·zero``); the caller broadcasts it over probes and adds any
    per-probe residual correction.
    """
    q = q.astype(jnp.float32)
    zero_base = jnp.zeros((q.shape[0],), jnp.float32)
    if backend in ("float", "fp16"):
        return q, zero_base
    if backend == "int8":
        qe = (q * params["scale"]).astype(jnp.bfloat16)
        return qe, q @ params["zero"]
    if backend == "onebit":
        signs = jnp.where(q >= 0, jnp.int8(1), jnp.int8(-1))
        if packed_width is None:
            raise ValueError("onebit queries need packed_width")
        pad = packed_width * 32 - signs.shape[-1]
        if pad:
            # pad signs with −1, matching the encoder's zero-bit padding:
            # every stored row gets the identical +0.25/pad-bit shift, so
            # rankings and values agree with the standalone binary_ip op
            signs = jnp.pad(signs, ((0, 0), (0, pad)),
                            constant_values=jnp.int8(-1))
        return signs, zero_base
    raise ValueError(f"unknown fused backend {backend!r}")


def fused_ivf_topk(probes: jax.Array, q: jax.Array,
                   list_storage: jax.Array, list_ids: jax.Array, k: int,
                   backend: str, params: Optional[dict] = None,
                   extra_base: Optional[jax.Array] = None,
                   use_pallas: bool = True,
                   interpret: Optional[bool] = None
                   ) -> tuple[jax.Array, jax.Array]:
    """(Q, k) top-k over the probed lists; float queries in, ids out.

    ``extra_base`` (Q, nprobe) adds a per-(query, probe) score correction
    (residual encoding's routed centroid term).  ``use_pallas=False`` runs
    the jnp reference (identical results); off-TPU the kernel runs with
    ``interpret=True``.
    """
    params = params or {}
    packed_width = list_storage.shape[-1] if backend == "onebit" else None
    qe, base_q = prepare_queries(q, backend, params,
                                 packed_width=packed_width)
    base = jnp.broadcast_to(base_q[:, None], probes.shape).astype(jnp.float32)
    if extra_base is not None:
        base = base + extra_base.astype(jnp.float32)
    if not use_pallas:
        return _ref.fused_ivf_topk_ref(probes.astype(jnp.int32), qe,
                                       list_storage, list_ids, base,
                                       k=k, backend=backend)
    interp = (jax.default_backend() != "tpu") if interpret is None \
        else interpret
    return fused_ivf_topk_pallas(probes.astype(jnp.int32), qe, list_storage,
                                 list_ids, base, k=k, backend=backend,
                                 interpret=interp)
