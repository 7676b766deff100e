"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (smoke tests see 1 CPU device; only the dry-run
process sets ``xla_force_host_platform_device_count``).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "POD_CHIPS", "describe"]

POD_CHIPS = 256  # 16 x 16 TPU v5e pod slice


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], **kwargs):
    """``jax.make_mesh`` with GSPMD (``AxisType.Auto``) axes.

    The scan steps declare in/out shardings and let the partitioner place
    everything between; ``jax.make_mesh`` defaults to explicit axes, under
    which their per-trait gathers (``take_along_axis`` over a marker-sharded
    tile) have no resolvable output sharding and are refused.
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes), **kwargs)


def make_production_mesh(*, multi_pod: bool = False):
    """(data=16, model=16) single pod; (pod=2, data=16, model=16) for two
    pods — 512 chips.  The 'pod' axis carries only data parallelism (DCN
    between pods is too slow for TP), which the sharding rules encode."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def describe(mesh) -> str:
    return "x".join(f"{a}={mesh.shape[a]}" for a in mesh.axis_names)
