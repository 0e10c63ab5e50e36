"""Production mesh definitions (single-pod 16×16, multi-pod 2×16×16).

A FUNCTION, not a module-level constant — importing this module never touches
jax device state (required so smoke tests see 1 device while the dry-run sees
512 placeholder devices).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the serving and dry-run
    paths place arrays by NamedSharding and let GSPMD propagate the rest,
    which explicit axes (jax's default) reject at the embed gather."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    import os
    override = os.environ.get("REPRO_MESH")  # e.g. "2,4" (CI-scale tests)
    if override:
        dims = tuple(int(x) for x in override.split(","))
        axes = (("pod", "data", "model") if len(dims) == 3
                else ("data", "model"))
        return _make_mesh(dims, axes)
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def data_axes(mesh) -> tuple:
    """The axes batches shard over (pods fold into data parallelism)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def axis_size(mesh, name) -> int:
    if isinstance(name, (tuple, list)):
        out = 1
        for n in name:
            out *= axis_size(mesh, n)
        return out
    return dict(zip(mesh.axis_names, mesh.devices.shape))[name]


def small_test_mesh(n_data: int = 2, n_model: int = 2):
    """Tiny mesh for CPU subprocess tests (requires host device override)."""
    return _make_mesh((n_data, n_model), ("data", "model"))


def _axes_for(ndim: int) -> tuple:
    axes = {2: ("data", "model"), 3: ("pod", "data", "model")}.get(ndim)
    if axes is None:
        raise ValueError(
            f"mesh shape needs 2 dims (data, model) or 3 (pod, data, "
            f"model), got {ndim} — e.g. --mesh 1,2 / REPRO_MESH=1,2")
    return axes


def parse_mesh_env(var: str = "REPRO_MESH"):
    """``ServeConfig.mesh_shape`` from the env (e.g. ``REPRO_MESH=1,2``).

    Returns None when unset/empty — the serving CLI and CI smoke use this so
    the same invocation runs unsharded by default and mesh-sharded under the
    2-host-device repro environment."""
    import os
    raw = os.environ.get(var, "").strip()
    if not raw:
        return None
    return tuple(int(x) for x in raw.split(","))


def make_serving_mesh(mesh_shape):
    """The engine's serving mesh: ``mesh_shape`` -> a real device mesh.

    None means "no mesh" (single-device engine, returns None). Anything else
    demands the devices exist: ``jax.make_mesh`` raises when the host exposes
    fewer devices than the shape needs, so a mis-set environment fails loudly
    instead of silently collapsing to one device."""
    if not mesh_shape:
        return None
    mesh_shape = tuple(int(d) for d in mesh_shape)
    return _make_mesh(mesh_shape, _axes_for(len(mesh_shape)))


class SimMesh:
    """Device-free stand-in for a mesh: only ``axis_names`` + device *shape*.

    ``Rules`` and :func:`axis_size` consult nothing else, so the offline
    memory profiler can bill per-device bytes for meshes far larger than the
    host (e.g. a simulated 2-GPU mesh inside a 1-CPU test process). Not
    usable for placement — ``Rules.named`` needs a real mesh."""

    class _Devices:
        def __init__(self, shape):
            self.shape = tuple(shape)
            self.size = 1
            for d in shape:
                self.size *= d

    def __init__(self, shape, axes=None):
        shape = tuple(int(d) for d in shape)
        self.axis_names = tuple(axes) if axes else _axes_for(len(shape))
        assert len(self.axis_names) == len(shape), (shape, self.axis_names)
        self.devices = SimMesh._Devices(shape)
        self.shape = dict(zip(self.axis_names, shape))
