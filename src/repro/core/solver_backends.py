"""Pluggable local-SDCA solver backends (docs/DESIGN.md §5).

Every engine — ``fit`` (core/dmtrl.py), ``fit_distributed``
(core/distributed.py) and the async engine (core/async_dmtrl.py) — reaches
the local subproblem (paper Algorithm 2) through this registry: a config
names a backend (``DMTRLConfig.solver``), the engine resolves it with
``get_backend`` and builds a per-task solver with ``backend.make``. All
backends share the contract

    solve(x, y, alpha_i, w_i, n_i, sigma_ii, key) -> (dalpha, r)

acting on ONE task's (padded) arrays, vmappable over the task axis, with
the H coordinate draws derived from ``key`` exactly as
``sdca.sample_coords`` does — so every backend produces the SAME sampled
coordinate order and (up to float-op ordering) the same iterate sequence.

Registered backends:

  naive        literal Algorithm 2, one coordinate per step (oracle).
  block_gram   jnp block-Gram form (docs/DESIGN.md §4): same iterates,
               MXU-shaped.
  pallas_block per-block Pallas kernel: one pallas_call per H-block,
               ``w``/``r`` re-streamed from HBM every block.
  pallas_round fused Pallas round kernel: ALL H/B blocks in one
               pallas_call, ``w``/``r`` VMEM-resident across blocks,
               coordinate sampling on-device (docs/DESIGN.md §6).

The Pallas backends cover only the losses with a closed-form kernel delta
(``kernels.sdca.SUPPORTED_LOSSES``) and refuse any other loss when they are
built; ``pallas_round`` also refuses a task block over its VMEM budget
(``kernels.sdca.sdca_kernel.ROUND_VMEM_BUDGET``) when it is traced. Neither
runs a jnp path in the kernel's place.

``naive`` and ``block_gram`` also run on packed task storage
(``packed=True``): ``make(..., n_cap=n_max)`` builds
``solve(x, y, alpha, w_i, n_i, sigma_ii, key, offset)`` over all tasks'
packed rows, reading sample j of the task at row ``offset + j``, with the
same coordinate draws. The Pallas backends run their kernels over a
task's padded rows and refuse packed data
(core/distributed.py:make_local_solve).

With a sharded feature dim (a ``model`` mesh axis) ``make`` gets that
axis as ``axis_name``: ``naive`` and ``block_gram`` psum their
d-contractions over it, and the Pallas backends, whose kernels compute
their own, refuse it.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .losses import Loss
from .sdca import (
    local_sdca_block,
    local_sdca_naive,
    sample_coords,
)

Array = jax.Array

# solve(x, y, alpha_i, w_i, n_i, sigma_ii, key) -> (dalpha, r)
Solver = Callable[..., Tuple[Array, Array]]


@dataclasses.dataclass(frozen=True)
class SolverBackend:
    """A named way to run one task's local SDCA round."""

    name: str
    description: str
    # H must be rounded up to a multiple of the block size
    block_aligned: bool
    # make(loss, rho, lam, H, block=..., axis_name=...) -> Solver
    make: Callable[..., Solver]
    # pallas_call launches per local round for given (H, block)
    pallas_calls: Callable[[int, int], int] = lambda H, block: 0
    # solve body contains pallas_call ops: shard_map engines must disable
    # replication checking around it (distributed.round_shard_map)
    uses_pallas: bool = False
    # runs on packed task storage (core/mtl_data.py:PackedMTLData): make
    # takes ``n_cap`` and solve a trailing ``offset``, the task's first row
    packed: bool = False

    def round_local_iters(self, H: int, block: int) -> int:
        """Round H up to this backend's alignment requirement."""
        if self.block_aligned:
            return int(np.ceil(H / block)) * block
        return H

    def pallas_calls_per_round(self, H: int, block: int) -> int:
        return self.pallas_calls(self.round_local_iters(H, block), block)


_REGISTRY: Dict[str, SolverBackend] = {}


def register_backend(backend: SolverBackend) -> SolverBackend:
    _REGISTRY[backend.name] = backend
    return backend


def get_backend(name: str) -> SolverBackend:
    try:
        return _REGISTRY[name]
    except KeyError as e:
        raise KeyError(
            f"unknown solver backend {name!r}; have {sorted(_REGISTRY)}"
        ) from e


def available_backends() -> Dict[str, SolverBackend]:
    return dict(sorted(_REGISTRY.items()))


def _check_kernel_loss(backend: str, loss: Loss) -> None:
    from repro.kernels.sdca import SUPPORTED_LOSSES  # lazy: kernel layer

    if loss.name not in SUPPORTED_LOSSES:
        raise ValueError(
            f"the {backend} backend has no kernel delta for loss "
            f"{loss.name!r} (kernel losses: {SUPPORTED_LOSSES}); use "
            f'solver="block_gram"'
        )


def _kappa(rho: float, lam: float, n_i: Array, sigma_ii: Array, dtype) -> Array:
    nf = jnp.maximum(n_i.astype(dtype), 1.0)
    return rho * sigma_ii / (lam * nf)


# ---------------------------------------------------------------------------
# naive — literal Algorithm 2 (reference semantics)
# ---------------------------------------------------------------------------
def _make_naive(
    loss: Loss,
    rho: float,
    lam: float,
    H: int,
    block: int = 64,
    axis_name: Optional[str] = None,
    n_cap: Optional[int] = None,
) -> Solver:
    def solve(x, y, alpha_i, w_i, n_i, sigma_ii, key, offset=None):
        coords = sample_coords(key, H, n_i, x.shape[0])
        return local_sdca_naive(
            x, y, alpha_i, w_i, n_i, sigma_ii, coords, rho, lam, loss, axis_name,
            offset=offset, n_cap=n_cap,
        )

    return solve


# ---------------------------------------------------------------------------
# block_gram — jnp block-Gram form (docs/DESIGN.md §4)
# ---------------------------------------------------------------------------
def _make_block_gram(
    loss: Loss,
    rho: float,
    lam: float,
    H: int,
    block: int = 64,
    axis_name: Optional[str] = None,
    n_cap: Optional[int] = None,
) -> Solver:
    def solve(x, y, alpha_i, w_i, n_i, sigma_ii, key, offset=None):
        coords = sample_coords(key, H, n_i, x.shape[0])
        return local_sdca_block(
            x, y, alpha_i, w_i, n_i, sigma_ii, coords, rho, lam, loss,
            block=block, axis_name=axis_name, offset=offset, n_cap=n_cap,
        )

    return solve


# ---------------------------------------------------------------------------
# pallas_block — per-block Pallas kernel (one pallas_call per H-block)
# ---------------------------------------------------------------------------
def _make_pallas_block(
    loss: Loss,
    rho: float,
    lam: float,
    H: int,
    block: int = 64,
    axis_name: Optional[str] = None,
) -> Solver:
    if axis_name is not None:
        raise ValueError(
            "the pallas_block backend computes its own d-contractions; with "
            "a sharded feature dim use block_gram (psum'ed) instead"
        )
    _check_kernel_loss("pallas_block", loss)
    from repro.kernels.sdca import ops as sdca_ops  # lazy: kernel layer

    def solve(x, y, alpha_i, w_i, n_i, sigma_ii, key):
        coords = sample_coords(key, H, n_i, x.shape[0])
        coords_b = coords.reshape(H // block, block)
        kappa = _kappa(rho, lam, n_i, sigma_ii, x.dtype)

        def blk_fn(carry, cb):
            dalpha, r = carry
            xb = x[cb]  # (B, d) gather
            at0 = alpha_i[cb] + dalpha[cb]
            deltas = sdca_ops.sdca_block_apply(
                xb, w_i, r, at0, y[cb], cb, kappa, loss.name
            ).astype(x.dtype)
            dalpha = dalpha.at[cb].add(deltas)
            return (dalpha, r + xb.T @ deltas), None

        dalpha0 = jnp.zeros_like(alpha_i) + y[0] * 0
        r0 = jnp.zeros_like(w_i) + x[0] * 0  # see local_sdca_naive note
        (dalpha, r), _ = jax.lax.scan(blk_fn, (dalpha0, r0), coords_b)
        return dalpha, r

    return solve


# ---------------------------------------------------------------------------
# pallas_round — fused whole-round Pallas kernel (ONE pallas_call)
# ---------------------------------------------------------------------------
def _make_pallas_round(
    loss: Loss,
    rho: float,
    lam: float,
    H: int,
    block: int = 64,
    axis_name: Optional[str] = None,
) -> Solver:
    if axis_name is not None:
        raise ValueError(
            "the pallas_round backend computes its own d-contractions; with "
            "a sharded feature dim use block_gram (psum'ed) instead"
        )
    _check_kernel_loss("pallas_round", loss)
    from repro.kernels.sdca import ops as sdca_ops  # lazy: kernel layer

    def solve(x, y, alpha_i, w_i, n_i, sigma_ii, key):
        # the kernel maps the key-derived uniform stream to coordinates
        # on-device with sample_coords' exact arithmetic (bit-equal draws)
        u = jax.random.uniform(key, (H,))
        kappa = _kappa(rho, lam, n_i, sigma_ii, x.dtype)
        dalpha, r = sdca_ops.sdca_round(
            x, y, alpha_i, w_i, u, n_i, kappa, loss.name, block=block
        )
        return dalpha.astype(alpha_i.dtype), r.astype(w_i.dtype)

    return solve


register_backend(
    SolverBackend(
        name="naive",
        description="literal Algorithm 2: one coordinate per step, d-dim "
        "inner product + axpy each (reference semantics)",
        block_aligned=False,
        make=_make_naive,
        packed=True,
    )
)
register_backend(
    SolverBackend(
        name="block_gram",
        description="jnp block-Gram form: three matmuls per B-block plus a "
        "B-step scalar recursion on the Gram block; same iterates as naive",
        block_aligned=True,
        make=_make_block_gram,
        packed=True,
    )
)
register_backend(
    SolverBackend(
        name="pallas_block",
        description="per-block Pallas kernel: one pallas_call per H-block, "
        "w/r re-streamed from HBM each block",
        block_aligned=True,
        make=_make_pallas_block,
        pallas_calls=lambda H, block: H // block,
        uses_pallas=True,
    )
)
register_backend(
    SolverBackend(
        name="pallas_round",
        description="fused Pallas round kernel: all H/B blocks in one "
        "pallas_call, w/r VMEM-resident, on-device coordinate sampling",
        block_aligned=True,
        make=_make_pallas_round,
        pallas_calls=lambda H, block: 1,
        uses_pallas=True,
    )
)
