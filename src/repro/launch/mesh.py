"""Production mesh construction.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state -- the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and only then builds the mesh.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

__all__ = ["make_production_mesh", "make_cpu_mesh", "mesh_axis_sizes"]


def _make_mesh(shape, axes) -> Mesh:
    """jax.make_mesh with explicit Auto axis types."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    Axes: (pod,) data, model.  ``pod`` is an outer data-parallel axis whose
    collectives cross the inter-pod interconnect (DCI); ``data`` is in-pod
    DP; ``model`` is tensor parallelism over the fastest ICI dimension.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_cpu_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Tiny mesh over however many (host) devices exist -- tests."""
    return _make_mesh((data, model), ("data", "model"))


def mesh_axis_sizes(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
