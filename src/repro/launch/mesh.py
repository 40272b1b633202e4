"""Production mesh construction (brief: MULTI-POD DRY-RUN step 1).

Defined as functions so importing this module never touches jax device
state (device count is locked at first backend init).

The coded path (dist/mesh_exec.py) treats the ``model`` axis as the
worker fleet: one coded piece per axis slice.  ``validate_pieces`` is the
typed front door for that mapping — callers get a ``PiecePlacementError``
naming n and the axis extent instead of a GSPMD shape failure deep inside
``shard_map``.
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_local_mesh", "dp_axes",
           "validate_pieces", "MODEL_AXIS", "PiecePlacementError"]

MODEL_AXIS = "model"


class PiecePlacementError(ValueError):
    """Coded pieces cannot be placed on the mesh (n > axis extent, bad
    axis name, or an invalid requested axis split)."""


def _auto_mesh(shape: tuple, axes: tuple) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with ``Auto`` axes: the coded programs leave
    sharding propagation to the compiler (an ``Explicit`` axis would make
    every gather name its output sharding)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(*, model: int | None = None) -> jax.sharding.Mesh:
    """(data, model) mesh on whatever devices exist (smoke tests, CPU).

    Default puts every device on the ``model`` axis — the coded-dispatch
    fleet.  ``model=`` overrides the model-axis extent; the remaining
    devices become the data axis, so ``model`` must divide the device
    count (validated here with a typed error, not a GSPMD failure).
    """
    ndev = len(jax.devices())
    if model is None:
        model = ndev
    if not 1 <= model <= ndev:
        raise PiecePlacementError(
            f"make_local_mesh: need 1 <= model <= {ndev} devices, "
            f"got model={model}")
    if ndev % model:
        raise PiecePlacementError(
            f"make_local_mesh: model={model} does not divide the "
            f"{ndev} available devices (the rest form the data axis)")
    return _auto_mesh((ndev // model, model), ("data", "model"))


def validate_pieces(mesh: jax.sharding.Mesh, n: int,
                    axis: str = MODEL_AXIS) -> int:
    """Check n coded pieces fit the mesh's worker axis; return its extent."""
    if axis not in mesh.shape:
        raise PiecePlacementError(
            f"mesh has no {axis!r} axis (axes: {tuple(mesh.axis_names)})")
    extent = int(mesh.shape[axis])
    if not 1 <= n <= extent:
        raise PiecePlacementError(
            f"cannot place {n} coded pieces on the {axis!r} axis: extent "
            f"is {extent} (one piece per device slice; shrink n or build "
            f"the mesh with a larger {axis!r} extent)")
    return extent


def dp_axes(mesh: jax.sharding.Mesh) -> tuple:
    """Every mesh axis except the model/worker axis — used for batch/seq."""
    return tuple(a for a in mesh.axis_names if a != MODEL_AXIS)
