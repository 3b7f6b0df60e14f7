"""Production mesh construction (assignment MULTI-POD DRY-RUN §1).

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module never touches jax device state. The dry-run launcher
sets XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax
import; ordinary smoke tests and benches see 1 device.

Every mesh is built with Auto axis types (:func:`make_auto_mesh`): the
sharding rules place arrays with ``with_sharding_constraint`` and leave
propagation to the compiler.
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType

__all__ = ["make_auto_mesh", "make_production_mesh",
           "make_small_mesh", "make_exchange_mesh", "dp_axes_for"]


def make_auto_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with every axis Auto-typed."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_small_mesh(data: int = 1, model: int = 1):
    """Tiny mesh for CPU tests (device count permitting)."""
    return make_auto_mesh((data, model), ("data", "model"))


def make_exchange_mesh(n_hosts: int | None = None, axis: str = "hosts"):
    """1-D mesh for the shard-exchange collectives (core/exchange.py).

    One position per participating host (in CI: per fake host device).
    Defaults to all visible devices.
    """
    if n_hosts is None:
        n_hosts = jax.device_count()
    return make_auto_mesh((n_hosts,), (axis,))


def dp_axes_for(mesh) -> tuple[str, ...]:
    """The data-parallel axes present in a mesh (pod spans pods)."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)
