"""DMTRL core: the paper's contribution as composable JAX modules.

The supported training surface is the engine-agnostic facade:

    from repro.core import DMTRLEstimator
    est = DMTRLEstimator(engine="distributed", mesh=mesh, loss="hinge")
    est.fit(train).score(test)

``fit`` / ``fit_distributed`` / ``fit_async`` remain importable as thin
deprecated wrappers over the same engine implementations.
"""
import functools as _functools
import warnings as _warnings

from .dmtrl import (
    DMTRLConfig,
    DMTRLResult,
    WarmStart,
    w_step,
    make_w_step_round,
)
from .dmtrl import fit as _fit_impl
from .distributed import (
    DistributedOptions,
    MeshAxes,
    make_distributed_round,
    make_local_solve,
    server_reduce,
)
from .distributed import fit_distributed as _fit_distributed_impl
from .async_dmtrl import AsyncOptions, make_async_tick
from .async_dmtrl import fit_async as _fit_async_impl
from .transport import (
    CommitReceipt,
    Snapshot,
    Transport,
    TransportSpec,
    available_transports,
    get_transport,
    register_transport,
)
from .gossip import (
    GossipTransport,
    build_adjacency,
    mixing_matrix,
    spectral_gap,
)
from .wire import (
    Codec,
    Encoded,
    ErrorFeedback,
    TransportProtocolError,
    available_codecs,
    get_codec,
)
from .engines import (
    Engine,
    EngineResult,
    available_engines,
    get_engine,
    register_engine,
)
from .estimator import DMTRLEstimator, NotFittedError
from .losses import Loss, get_loss, registered_losses
from .mtl_data import (
    MTLData,
    PackedMTLData,
    from_task_list,
    normalize_rows,
    pack_tasks,
)
from .omega import (
    correlation_from_sigma,
    init_sigma,
    omega_step,
    omega_step_lowrank,
    rho_lemma10,
    rho_spectral,
)
from .sigma_view import (
    DenseSigma,
    LowRankDiagSigma,
    SigmaView,
    SparseSigma,
    as_view,
    maybe_dense,
    view_from_factors,
)
from .omega_regularizers import (
    OmegaRegularizer,
    available_regularizers,
    get_regularizer,
    register_regularizer,
)
from .solver_backends import (
    SolverBackend,
    available_backends,
    get_backend,
    register_backend,
)
from . import (
    baselines,
    convergence,
    dual,
    engines,
    estimator,
    feature_maps,
    omega_regularizers,
    sdca,
    sigma_view,
    solver_backends,
)
from . import transport  # noqa: F401 (registry module, part of the API)


def _deprecated(fn, replacement: str):
    @_functools.wraps(fn)
    def wrapper(*args, **kwargs):
        _warnings.warn(
            f"repro.core.{fn.__name__} is deprecated; use {replacement} "
            "(see docs/DESIGN.md §8 for the migration table)",
            DeprecationWarning,
            stacklevel=2,
        )
        return fn(*args, **kwargs)

    wrapper.__doc__ = (
        f"Deprecated: use {replacement}.\n\n{fn.__doc__ or ''}"
    )
    return wrapper


fit = _deprecated(_fit_impl, 'DMTRLEstimator(engine="reference").fit')
fit_distributed = _deprecated(
    _fit_distributed_impl, 'DMTRLEstimator(engine="distributed", mesh=...).fit'
)
fit_async = _deprecated(
    _fit_async_impl,
    'DMTRLEstimator(engine="async", mesh=..., '
    "async_options=AsyncOptions(...)).fit",
)

__all__ = [
    "DMTRLConfig",
    "DMTRLResult",
    "DMTRLEstimator",
    "NotFittedError",
    "WarmStart",
    "fit",
    "w_step",
    "make_w_step_round",
    "MeshAxes",
    "DistributedOptions",
    "AsyncOptions",
    "fit_distributed",
    "make_distributed_round",
    "make_local_solve",
    "server_reduce",
    "fit_async",
    "make_async_tick",
    "Transport",
    "TransportSpec",
    "CommitReceipt",
    "Snapshot",
    "available_transports",
    "get_transport",
    "register_transport",
    "GossipTransport",
    "build_adjacency",
    "mixing_matrix",
    "spectral_gap",
    "Codec",
    "Encoded",
    "ErrorFeedback",
    "TransportProtocolError",
    "available_codecs",
    "get_codec",
    "Engine",
    "EngineResult",
    "available_engines",
    "get_engine",
    "register_engine",
    "OmegaRegularizer",
    "available_regularizers",
    "get_regularizer",
    "register_regularizer",
    "Loss",
    "get_loss",
    "registered_losses",
    "MTLData",
    "PackedMTLData",
    "pack_tasks",
    "from_task_list",
    "normalize_rows",
    "correlation_from_sigma",
    "init_sigma",
    "omega_step",
    "omega_step_lowrank",
    "rho_lemma10",
    "rho_spectral",
    "SigmaView",
    "DenseSigma",
    "LowRankDiagSigma",
    "SparseSigma",
    "as_view",
    "maybe_dense",
    "view_from_factors",
    "SolverBackend",
    "available_backends",
    "get_backend",
    "register_backend",
    "baselines",
    "convergence",
    "dual",
    "engines",
    "estimator",
    "feature_maps",
    "omega_regularizers",
    "sdca",
    "sigma_view",
    "solver_backends",
    "transport",
    "gossip",
    "wire",
]
