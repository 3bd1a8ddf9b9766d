"""Production mesh construction.

Kept as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required because the dry-run overrides the
device count via XLA_FLAGS before first jax init while tests/benches must
see the single real CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: the aggregation path is
    written for compiler-propagated (GSPMD) sharding, and JAX's default
    ``Explicit`` axes reject its replicated (n, n) statistics."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16×16 = 256 chips per pod; 2 pods = 512 chips when ``multi_pod``."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """("data", "model") mesh over whatever host devices exist.

    The device count is factored into the most-square (data, model) split
    with data <= model — so the forced-8-device CPU mesh
    (``XLA_FLAGS=--xla_force_host_platform_device_count=8``) becomes 2×4
    and exercises *both* the worker-axis and the d-axis sharding of the
    mesh-native aggregation path (DESIGN.md §10); a single real device
    degenerates to 1×1.
    """
    n = len(jax.devices())
    data = 1
    while n % (data * 2) == 0 and data * 2 <= n // (data * 2):
        data *= 2
    return make_mesh((data, n // data), ("data", "model"))


def data_parallel_size(mesh: Mesh) -> int:
    """Number of byzantine-game workers the mesh supports (pod×data)."""
    size = mesh.shape["data"]
    if "pod" in mesh.axis_names:
        size *= mesh.shape["pod"]
    return size
