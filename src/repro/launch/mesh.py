"""Production mesh construction (multi-pod dry-run target)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: the arrays that shard_map programs return stay plain
    # (untyped) shardings, so eager ops on them work.  Explicit axes, the
    # default of jax.make_mesh since JAX 0.7, make an eager op mixing such a
    # result with a single-device array fail ("Resource axis ... not found").
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


# the chip the production mesh models (the dry runs' roofline peaks)
PRODUCTION_KIND = "TPU v5 lite"


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (=256 chips/pod) single-pod mesh, or 2x16x16 two-pod mesh.

    A FUNCTION (not a module constant) so importing this module never touches
    jax device state.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def data_axes_of(mesh) -> tuple:
    """All non-'model' axes act as data/FSDP axes."""
    return tuple(a for a in mesh.axis_names if a != "model")


def data_mesh(n: int | None = None, name: str = "data"):
    """1-D data mesh over the first n devices (default: all of them)."""
    return _mesh((n or len(jax.devices()),), (name,))
