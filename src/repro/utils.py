"""Shared utilities: chunked evaluation, PRNG plumbing, pytree helpers."""

from __future__ import annotations

import functools
import os
from pathlib import Path
from typing import Any, Callable, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np


#: JAX's persistent compilation cache when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: a fixed path, since the path is part of every cache key.
REPO_COMPILATION_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compilation_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    this leaves it alone.  Otherwise the cache goes to the repo's
    ``.jax_cache``.  Entry points that run on the accelerator call this;
    importing ``repro`` never does, so tests and ahead-of-time compiles
    neither read nor write the cache.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_COMPILATION_CACHE))
    return str(REPO_COMPILATION_CACHE)


def rng_seq(seed: int) -> Iterable[jax.Array]:
    """Infinite deterministic stream of PRNG keys."""
    key = jax.random.PRNGKey(seed)
    while True:
        key, sub = jax.random.split(key)
        yield sub


def chunked(n: int, chunk: int) -> Iterable[tuple[int, int]]:
    """Yield (start, stop) covering [0, n) in chunks."""
    for start in range(0, n, chunk):
        yield start, min(start + chunk, n)


@functools.partial(jax.jit, donate_argnums=0)
def _write_rows(out: jax.Array, rows: jax.Array, start) -> jax.Array:
    return jax.lax.dynamic_update_slice_in_dim(out, rows, start, axis=0)


def chunked_map(fn: Callable[[jax.Array], jax.Array], x: jax.Array,
                chunk: int = 65536) -> jax.Array:
    """Apply ``fn`` over the leading axis of ``x`` in chunks and concatenate.

    Used for streaming transforms over indexes too large to process at once.
    Each chunk's result is written in place into one preallocated output,
    so the device holds the output plus one chunk — a concatenation would
    hold every chunk and the output at once (at 2.1M 768-d rows, 12 GB of
    a 16 GB chip).
    """
    n = x.shape[0]
    if n <= chunk:
        return fn(x)
    out = None
    for s, e in chunked(n, chunk):
        rows = fn(x[s:e])
        if out is None:
            out = jnp.zeros((n,) + rows.shape[1:], rows.dtype)
        out = _write_rows(out, rows, s)
    return out


def tree_size_bytes(tree: Any) -> int:
    """Total size in bytes of all array leaves."""
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree)
               if hasattr(x, "dtype"))


def tree_num_params(tree: Any) -> int:
    return sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(tree)
               if hasattr(x, "shape"))


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB", "PiB"):
        if abs(n) < 1024.0:
            return f"{n:.2f} {unit}"
        n /= 1024.0
    return f"{n:.2f} EiB"


def human_count(n: float) -> str:
    for unit in ("", "K", "M", "B", "T"):
        if abs(n) < 1000.0:
            return f"{n:.2f}{unit}"
        n /= 1000.0
    return f"{n:.2f}Q"


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


def first_divisor_leq(n: int, cap: int) -> int:
    """Largest divisor of ``n`` that is <= cap (>=1)."""
    for d in range(min(cap, n), 0, -1):
        if n % d == 0:
            return d
    return 1


@functools.cache
def cached_jit(fn, **kwargs):
    return jax.jit(fn, **kwargs)


def stable_hash(items: Sequence[Any]) -> int:
    """Order-dependent deterministic hash for seeding from config fields."""
    h = 1469598103934665603
    for it in items:
        for b in repr(it).encode():
            h ^= b
            h = (h * 1099511628211) % (1 << 64)
    return h % (1 << 31)
