"""Process start-up shared by the benchmark's entry points."""

import os
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def configure_jax():
    """Import JAX with the benchmark's settings and return it.

    JAX's persistent compilation cache lives at a fixed path inside the
    checkout, so only a cell's first run there compiles and nothing is
    shared outside it; every program is cached, however fast it compiled.
    The TPU runtime writes no logs (it would write them under /tmp).
    """
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    cache = ROOT / ".jax_cache"
    cache.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax
