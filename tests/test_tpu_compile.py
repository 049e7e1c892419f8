"""Ahead-of-time compiles of the served Pallas kernels for a v5e chip.

The interpret-mode parity tests cannot see what only the chip's compiler
refuses: casts Mosaic has no lowering for, block shapes off the (8, 128)
tiling, more VMEM than a kernel may hold.  Each case here compiles one
kernel of the main search path for a *described* (not attached) v5e chip at
the widths ``chip_smoke.py`` serves — the paper's 2.1M-doc corpus at
PCA-128 int8 (24×) and PCA-245 1-bit (100×), and an IVF index with 2048
lists probed 64 at a time — and checks that the program holds the kernel
(``tpu_custom_call``) and fits the chip's 16 GiB.

The topology is described only inside the module fixture: the library that
describes it may be loaded by one process at a time, and every test worker
imports this file.
"""

import functools
import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.binary_ip.kernel import binary_ip_pallas
from repro.kernels.int8_ip.kernel import int8_ip_pallas
from repro.kernels.ivf_fused.kernel import fused_ivf_topk_pallas

N_DOCS = 2_100_000           # configs/paper_dpr.py
N_QUERIES = 64               # the service's micro-batch cap
NLIST, NPROBE, K = 2048, 64, 10
# balanced lists cap at 1.25× the mean list: a lane-unaligned width
MAX_LEN = math.ceil(1.25 * N_DOCS / NLIST)
HBM_BYTES = 16 * 2**30       # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")      # no compiler logs on disk
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:       # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # an AOT compile is written to the persistent cache but cannot be
        # read back without a chip: keep the cache out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _ivf_case(backend, dq, w, storage_dtype, query_dtype):
    return (functools.partial(fused_ivf_topk_pallas, k=K, backend=backend),
            [((N_QUERIES, NPROBE), jnp.int32),
             ((N_QUERIES, dq), query_dtype),
             ((NLIST, MAX_LEN, w), storage_dtype),
             ((NLIST, MAX_LEN), jnp.int32),
             ((N_QUERIES, NPROBE), jnp.float32)])


CASES = {
    # exact pca_int8: (q ⊙ scale) bf16 × uint8 codes
    "int8_ip": (int8_ip_pallas,
                [((N_QUERIES, 128), jnp.bfloat16),
                 ((N_DOCS, 128), jnp.uint8)]),
    # exact pca_onebit: PCA-245 padded to 256 bits = 8 packed words
    "binary_ip": (binary_ip_pallas,
                  [((N_QUERIES, 256), jnp.int8),
                   ((N_DOCS, 8), jnp.uint32)]),
    "ivf_fused_int8": _ivf_case("int8", 128, 128, jnp.uint8, jnp.bfloat16),
    "ivf_fused_onebit": _ivf_case("onebit", 256, 8, jnp.uint32, jnp.int8),
    "ivf_fused_float": _ivf_case("float", 128, 128, jnp.float32,
                                 jnp.float32),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = CASES[case]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, f"{case}: {used / 2**30:.2f} GiB"
