"""DMTRL core: the paper's contribution as composable JAX modules.

The supported training surface is the engine-agnostic facade:

    from repro.core import DMTRLEstimator
    est = DMTRLEstimator(engine="distributed", mesh=mesh, loss="hinge")
    est.fit(train).score(test)

Each engine's own function lives in its module: ``core.dmtrl.fit``,
``core.distributed.fit_distributed`` and ``core.async_dmtrl.fit_async``.
"""
from .dmtrl import (
    DMTRLConfig,
    DMTRLResult,
    WarmStart,
    w_step,
    make_w_step_round,
)
from .distributed import (
    MeshAxes,
    make_distributed_round,
    make_local_solve,
    server_reduce,
)
from .async_dmtrl import AsyncOptions, make_async_tick
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


__all__ = [
    "DMTRLConfig",
    "DMTRLResult",
    "DMTRLEstimator",
    "NotFittedError",
    "WarmStart",
    "w_step",
    "make_w_step_round",
    "MeshAxes",
    "AsyncOptions",
    "make_distributed_round",
    "make_local_solve",
    "server_reduce",
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
