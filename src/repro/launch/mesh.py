"""Production mesh builders. Functions (not module constants) so importing
never touches jax device state."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axes. JAX 0.9 defaults to
    ``Explicit`` axes, under which ``with_sharding_constraint`` (what
    ``ShardingCtx.act`` places activations with) refuses the mesh."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a leading "pod"
    axis (the paper's chip-to-chip pipeline / cross-pod data axis)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — tests only."""
    return _auto_mesh((data, model), ("data", "model"))
