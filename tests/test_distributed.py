"""Multi-device behaviour (8 forced host devices, subprocess-isolated so the
main test process keeps its single-device jax)."""

import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_with_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_sharded_search_matches_single_host():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_test_mesh
        from repro.retrieval.sharded import make_distributed_search, shard_index
        from repro.retrieval.topk import topk_search

        rng = np.random.default_rng(0)
        docs = jnp.asarray(rng.standard_normal((512, 32)), jnp.float32)
        queries = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
        mesh = make_test_mesh(8, model=4)           # data=2, model=4
        search = make_distributed_search(mesh, k=10)
        docs_sharded = shard_index(docs, mesh, doc_axis="model")
        vals, idx = search(queries, docs_sharded)
        want_vals, want_idx = topk_search(queries, docs, 10)
        np.testing.assert_allclose(np.asarray(vals), np.asarray(want_vals),
                                   rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
        print("SHARDED_SEARCH_OK")
    """)
    assert "SHARDED_SEARCH_OK" in out


def test_distributed_pca_matches_local():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.pca import PCA, fit_pca_distributed
        from repro.launch.mesh import make_test_mesh

        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((800, 24)), jnp.float32)
        mesh = make_test_mesh(8, model=2)
        xs = jax.device_put(x, NamedSharding(mesh, P("data")))
        dist = fit_pca_distributed(xs, 6, mesh)
        local = PCA(6).fit(x)
        cos = np.abs(np.sum(np.asarray(dist.state["components"])
                            * np.asarray(local.state["components"]), axis=0))
        np.testing.assert_allclose(cos, 1.0, atol=1e-3)
        print("DIST_PCA_OK")
    """)
    assert "DIST_PCA_OK" in out


def test_compressed_grad_exchange_error_feedback():
    out = run_with_devices("""
        import functools
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_test_mesh
        from repro.parallel.compression_comm import (
            make_compressed_grad_exchange, init_residual)

        mesh = make_test_mesh(8, model=1)       # pure DP over "data"
        rng = np.random.default_rng(2)
        grads_steps = jnp.asarray(rng.standard_normal((20, 8, 64)),
                                  jnp.float32)   # (steps, shards, dim)

        def run(scheme):
            exchange = make_compressed_grad_exchange(scheme, "data")
            def one_host(gs):                       # gs (steps, 1, dim)
                res = jnp.zeros((64,))
                acc = jnp.zeros((64,))
                for t in range(20):
                    g = {"w": gs[t, 0]}
                    mean, res = exchange(g, res)
                    acc = acc + mean["w"]
                return acc[None]
            from repro.parallel.compat import shard_map
            fn = shard_map(one_host, mesh=mesh,
                           in_specs=P(None, "data", None),
                           out_specs=P("data", None))
            return np.asarray(fn(grads_steps))[0]

        exact = run("none")
        for scheme in ("int8", "onebit"):
            approx = run(scheme)
            # error feedback keeps the accumulated mean close to exact
            rel = np.linalg.norm(approx - exact) / np.linalg.norm(exact)
            print(scheme, "rel", rel)
            assert rel < (0.02 if scheme == "int8" else 0.35), (scheme, rel)
        print("COMPRESSED_COMM_OK")
    """)
    assert "COMPRESSED_COMM_OK" in out


def test_small_mesh_dryrun_lm():
    """End-to-end mini dry-run: reduced LM train on an 8-device mesh."""
    out = run_with_devices("""
        import jax, numpy as np
        from repro.configs.registry import get_arch
        from repro.launch.mesh import make_test_mesh
        from repro.launch.steps import build_step
        from repro.parallel.sharding import SINGLE_POD_RULES

        mesh = make_test_mesh(8, model=2)
        arch = get_arch("dbrx-132b")
        bundle = build_step(arch, arch.shape("train_4k"), mesh,
                            SINGLE_POD_RULES, reduced=True)
        with mesh:
            compiled = bundle.lower(mesh).compile()
        cost = compiled.cost_analysis()
        assert cost.get("flops", 0) > 0
        hlo = compiled.as_text()
        assert any(c in hlo for c in ("all-reduce", "all-gather")), \
            "expected collectives in sharded train step"
        print("MINI_DRYRUN_OK")
    """)
    assert "MINI_DRYRUN_OK" in out


def test_collective_bytes_parser_on_real_hlo():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_test_mesh
        from repro.launch.roofline import collective_bytes

        mesh = make_test_mesh(8, model=4)
        x = jnp.ones((32, 64), jnp.float32)
        xs = jax.device_put(x, NamedSharding(mesh, P("data", "model")))

        @jax.jit
        def f(a):
            return jnp.sum(a)          # cross-device reduction

        compiled = f.lower(xs).compile()
        coll = collective_bytes(compiled.as_text())
        assert coll["total"] > 0, compiled.as_text()[:2000]
        print("COLL_PARSE_OK", coll["total"])
    """)
    assert "COLL_PARSE_OK" in out
