"""Record the small chip trace that ``bench/tests/test_traces.py`` reduces.

    python bench/tests/data/record_trace.py <out_dir>

On one TPU: twelve searches of a 2.1M-row uint8 table (a bf16 matmul and a
top-k), six of 64 rows and six of 1, each inside the benchmark's
``bench.collect`` span, with a 20 ms ``bench.submit`` span of host work
(sleep) before each, recorded with the harness's profiler options.  Prints
the calls it made; copy ``<out_dir>/**/*.xplane.pb`` to
``bench/tests/data/sample.xplane.pb``.
"""

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def main(out_dir: str) -> int:
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace: needs a TPU")
    key = jax.random.key(0)
    table = jax.random.randint(key, (2_100_000, 128), 0, 255).astype(jnp.uint8)

    @jax.jit
    def search(q):
        s = jnp.dot(q.astype(jnp.bfloat16), table.astype(jnp.bfloat16).T,
                    preferred_element_type=jnp.float32)
        return jax.lax.top_k(s, 10)

    qs = np.asarray(jax.random.normal(key, (64, 128)))
    for rows in (1, 64):
        jax.block_until_ready(search(qs[:rows]))
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 2
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    t0 = time.perf_counter()
    for i in range(12):
        with jax.profiler.TraceAnnotation("bench.submit"):
            time.sleep(0.02)
        with jax.profiler.TraceAnnotation("bench.collect"):
            jax.block_until_ready(search(qs[: 1 if i % 2 else 64]))
    window = time.perf_counter() - t0
    jax.profiler.stop_trace()
    print(f"record_trace: 12 searches (6 x 64 rows, 6 x 1 row), "
          f"window {window:.6f} s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
