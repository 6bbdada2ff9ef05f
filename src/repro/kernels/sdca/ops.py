"""jit-level entry points for the SDCA Pallas kernels.

Used by the solver-backend registry (repro.core.solver_backends):

  * ``sdca_block_apply``  — one H-block of sampled coordinates; backs the
    ``pallas_block`` backend (one pallas_call per block).
  * ``sdca_round``        — one fused local round (all H/B blocks in a
    single pallas_call); backs the ``pallas_round`` backend.

Both compile for the TPU and interpret only on the CPU
(``repro.kernels.interpret_mode``). Losses outside ``SUPPORTED_LOSSES`` (no
closed-form delta in the kernel) raise; the backends refuse them when they
are built.
"""
from __future__ import annotations

import jax

from .. import interpret_mode
from .sdca_kernel import sdca_block_kernel, sdca_round_kernel

Array = jax.Array


def sdca_block_apply(
    xb: Array,  # (B, d) sampled rows
    w: Array,  # (d,)
    r: Array,  # (d,) running block correction
    at0: Array,  # (B,) initial alpha~ per slot
    y: Array,  # (B,)
    cb: Array,  # (B,) coordinate ids (duplicate detection)
    kappa: Array,  # scalar
    loss_name: str,
) -> Array:
    """Deltas for ONE block; the caller scatters them and updates r."""
    return sdca_block_kernel(
        xb, w, r, at0, y, cb, kappa, loss_name, interpret=interpret_mode()
    )


def sdca_round(
    x: Array,  # (n_max, d) full task block
    y: Array,  # (n_max,)
    alpha_i: Array,  # (n_max,)
    w: Array,  # (d,)
    u: Array,  # (H,) per-round uniform stream
    n_i: Array,  # scalar int
    kappa: Array,  # scalar
    loss_name: str,
    block: int = 64,
):
    """(dalpha, r) for one fused local round (single pallas_call)."""
    return sdca_round_kernel(
        x, y, alpha_i, w, u, n_i, kappa, loss_name,
        interpret=interpret_mode(), block=block,
    )
