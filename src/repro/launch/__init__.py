"""Launchers: production mesh, dry-run, training CLI.

NOTE: do NOT import .dryrun here — it sets XLA_FLAGS at import time and must
only be imported as the __main__ module of a fresh process.
"""
from .mesh import (
    HBM_BW,
    ICI_BW,
    PEAK_FLOPS_BF16,
    make_host_mesh,
    make_mesh,
    make_production_mesh,
    require_auto_axes,
)

__all__ = [
    "HBM_BW",
    "ICI_BW",
    "PEAK_FLOPS_BF16",
    "make_host_mesh",
    "make_mesh",
    "make_production_mesh",
    "require_auto_axes",
]
