"""Mesh construction (functions, not module constants, so importing never
touches jax device state).

Every mesh in this repo is built by ``make_mesh``: ``jax.make_mesh`` gives
Explicit-typed axes by default, and the mesh engines need Auto axes (their
host-side Sigma algebra and the shard_map round bodies leave the sharding
of intermediates to the compiler).
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(
    shape: Sequence[int], axes: Sequence[str], *, devices=None
) -> Mesh:
    """A mesh with Auto-typed axes over ``devices`` (default: all)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(AxisType.Auto,) * len(axes), devices=devices,
    )


def require_auto_axes(mesh: Mesh) -> None:
    """Reject a mesh with Explicit- or Manual-typed axes, naming them."""
    typed = dict(zip(mesh.axis_names, mesh.axis_types))
    bad = {name: t.name for name, t in typed.items() if t != AxisType.Auto}
    if bad:
        raise ValueError(
            f"the mesh engines need Auto-typed mesh axes, got {bad}; build "
            "the mesh with repro.launch.mesh.make_mesh"
        )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — tests/examples."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"need {data * model} devices, have {n}")
    return make_mesh((data, model), ("data", "model"))


# TPU v5e-class hardware constants used by the roofline (docs/DESIGN.md §Roofline)
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link
