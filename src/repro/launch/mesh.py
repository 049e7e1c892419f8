"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
initialization, and smoke tests must keep seeing 1 device.
"""

from __future__ import annotations


import jax

from repro.parallel.sharding import (AxisRules, MULTI_POD_RULES,
                                     SINGLE_POD_RULES)


def make_production_mesh(*, multi_pod: bool = False):
    """v5e-256 pod mesh: (data=16, model=16); two pods add a leading
    "pod" axis: (pod=2, data=16, model=16)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def rules_for_mesh(mesh) -> AxisRules:
    return MULTI_POD_RULES if "pod" in mesh.axis_names else SINGLE_POD_RULES


def make_test_mesh(n_devices: int = 8, model: int = 2):
    """Small mesh for unit tests (requires forced host devices)."""
    data = n_devices // model
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=_auto(2))


def _auto(n: int) -> tuple:
    """``Auto`` axes: ``jax.make_mesh`` defaults to ``Explicit`` ones, on
    which a contraction over a sharded axis (``x.T @ x`` in the PCA
    moments) raises instead of letting the compiler insert the reduce."""
    return (jax.sharding.AxisType.Auto,) * n
