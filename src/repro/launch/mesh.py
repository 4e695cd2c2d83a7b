"""Production meshes (assignment-mandated shapes) + the one mesh constructor.

A FUNCTION, not a module constant: importing this module never touches jax
device state.  Every mesh in the repo is built by :func:`make_compat_mesh`
with explicitly Auto-typed axes.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_compat_mesh(shape, axes, devices=None):
    """Mesh of ``shape`` over ``devices`` (default: all) with Auto axes."""
    axis_types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(tuple(shape), tuple(axes), axis_types=axis_types)
    import numpy as np

    from jax.sharding import Mesh

    devs = np.asarray(devices).reshape(shape)
    return Mesh(devs, tuple(axes), axis_types=axis_types)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    ndev = 512 if multi_pod else 256
    devices = jax.devices()[:ndev]
    return make_compat_mesh(shape, axes, devices)


def dp_axes_of(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def serve_mesh(data: int, model: int, devices=None):
    """One serve-replica mesh: ``(data, model)`` with the repo's canonical
    axis names, wrapped in a ready ``ParallelContext`` (``dp_axes`` from
    :func:`dp_axes_of`, so the replica/data split follows the same rule the
    trainer uses).  ``models/serve.py::cache_shardings`` then shards the
    paged pool's kv heads over ``model`` and per-slot state over ``data``;
    multiple replicas each call this with their own device slice and sit
    behind ``launch/router.py``."""
    need = data * model
    have = len(jax.devices() if devices is None else devices)
    if have < need:
        raise ValueError(
            f"serve_mesh({data}, {model}) needs {need} devices but only "
            f"{have} are visible (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need} before importing "
            f"jax to fake them on CPU)")
    mesh = make_compat_mesh((data, model), ("data", "model"), devices)
    from repro.core.parallel import ParallelContext

    return ParallelContext.for_mesh(mesh)
