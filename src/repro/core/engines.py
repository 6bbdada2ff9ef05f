"""Training-engine registry: one facade contract over the three drivers.

Mirrors the solver-backend registry (docs/DESIGN.md §5): a config names an
engine, the estimator resolves it with ``get_engine`` and calls the uniform

    engine.run(cfg, data, mesh=..., axes=..., options=..., regularizer=...,
               init=..., track=...) -> EngineResult

contract. The registered engines wrap the existing drivers bit-identically
(the adapters only normalize signatures and returns — parity-tested):

  reference    single-process Algorithm 1 (core/dmtrl.py:fit); the
               semantic oracle. No mesh, no options.
  distributed  parameter-server W-step on a JAX mesh
               (core/distributed.py:fit_distributed); mesh and axes, no
               options.
  async        bounded-staleness SSP engine
               (core/async_dmtrl.py:fit_async); AsyncOptions.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from .async_dmtrl import AsyncOptions, fit_async as _fit_async
from .distributed import MeshAxes, fit_distributed as _fit_distributed
from .dmtrl import DMTRLConfig, WarmStart, fit as _fit_reference
from .mtl_data import MTLData
from .sigma_view import SigmaView, maybe_dense
from ..launch.mesh import make_mesh
from ..obs.trace import span


@dataclasses.dataclass
class EngineResult:
    """Engine-agnostic fit result, always at the RAW (unpadded) problem
    size regardless of mesh padding — what the estimator stores."""

    W: np.ndarray  # (m, d) task weight rows
    alpha: np.ndarray  # (m, n_max) dual variables
    sigma: np.ndarray  # (m, m) task covariance; a SigmaView at huge m
    omega: Optional[np.ndarray]  # (m, m) task precision; None when the
    #               structured member has no cheap inverse at this size
    history: Dict[str, np.ndarray]
    rho_per_outer: Optional[List[float]] = None  # reference engine only
    # structured runs also expose the factors (SigmaView) directly
    sigma_view: Optional[SigmaView] = None


@dataclasses.dataclass(frozen=True)
class Engine:
    """A named way to run Algorithm 1 end to end."""

    name: str
    description: str
    needs_mesh: bool
    options_cls: Optional[type]
    # run(cfg, data, *, mesh, axes, options, regularizer, init, track)
    run: Callable[..., EngineResult]


_REGISTRY: Dict[str, Engine] = {}


def register_engine(engine: Engine) -> Engine:
    _REGISTRY[engine.name] = engine
    return engine


def get_engine(name: str) -> Engine:
    try:
        return _REGISTRY[name]
    except KeyError as e:
        raise KeyError(
            f"unknown engine {name!r}; have {sorted(_REGISTRY)}"
        ) from e


def available_engines() -> Dict[str, Engine]:
    return dict(sorted(_REGISTRY.items()))


def _default_mesh(axes: MeshAxes):
    """A 1-device mesh so mesh engines stay usable without ceremony."""
    return make_mesh((1,), (axes.data,))


def _unpad_state(state, raw: MTLData) -> tuple:
    """(alpha, omega) rows/cols of the REAL tasks from padded mesh state;
    over packed rows, alpha in the raw container's row order."""
    alpha = np.asarray(state.alpha)
    if raw.layout == "packed":
        if state.rows is not None:
            alpha = alpha[state.rows]
    else:
        alpha = alpha[: raw.m, : raw.n_max]
    if state.omega is None:
        omega = None
    elif isinstance(state.omega, SigmaView):
        omega = maybe_dense(state.omega.unpad(raw.m))
    else:
        omega = np.asarray(state.omega)[: raw.m, : raw.m]
    return alpha, omega


def _run_reference(
    cfg: DMTRLConfig,
    data: MTLData,
    *,
    mesh=None,
    axes: Optional[MeshAxes] = None,
    options: Any = None,
    regularizer=None,
    init: Optional[WarmStart] = None,
    track: bool = True,
) -> EngineResult:
    if mesh is not None or axes is not None or options is not None:
        raise ValueError(
            "the reference engine runs single-process: mesh/axes/options "
            'are distributed-only (use engine="distributed" or "async")'
        )
    with span("engine_run", cat="driver", engine="reference"):
        res = _fit_reference(
            cfg, data, track=track, init=init, regularizer=regularizer
        )
    return EngineResult(
        W=np.asarray(res.W),
        alpha=np.asarray(res.alpha),
        sigma=maybe_dense(res.sigma),
        omega=maybe_dense(res.omega),
        history=res.history,
        rho_per_outer=list(res.rho_per_outer),
        sigma_view=res.sigma_view,
    )


def _make_mesh_run(
    fit_fn: Callable, engine_name: str
) -> Callable[..., EngineResult]:
    """One adapter for both mesh engines: resolve a default mesh, forward
    to the driver (which resolves axes itself, and takes ``options`` when
    its engine has an options class), unpad, pack EngineResult."""

    def run(
        cfg: DMTRLConfig,
        data: MTLData,
        *,
        mesh=None,
        axes: Optional[MeshAxes] = None,
        options=None,
        regularizer=None,
        init: Optional[WarmStart] = None,
        track: bool = True,
    ) -> EngineResult:
        if mesh is None:
            mesh = _default_mesh(axes or MeshAxes())
        kw = {} if options is None else {"options": options}
        with span("engine_run", cat="driver", engine=engine_name):
            W, sigma, state, hist = fit_fn(
                cfg, data, mesh, axes, track=track, init=init,
                regularizer=regularizer, **kw,
            )
        alpha, omega = _unpad_state(state, data)
        sigma_view = None
        if isinstance(state.sigma, SigmaView):
            sigma_view = state.sigma.unpad(data.m)
        return EngineResult(
            W=np.asarray(W), alpha=alpha, sigma=maybe_dense(sigma),
            omega=omega, history=hist, sigma_view=sigma_view,
        )

    return run


_run_distributed = _make_mesh_run(_fit_distributed, "distributed")
_run_async = _make_mesh_run(_fit_async, "async")


register_engine(
    Engine(
        name="reference",
        description="single-process Algorithm 1 (vmap over tasks); the "
        "semantic oracle the mesh engines are tested against",
        needs_mesh=False,
        options_cls=None,
        run=_run_reference,
    )
)
register_engine(
    Engine(
        name="distributed",
        description="parameter-server W-step sharded over a JAX mesh "
        "(data/model/pod axes); bulk-synchronous rounds",
        needs_mesh=True,
        options_cls=None,
        run=_run_distributed,
    )
)
register_engine(
    Engine(
        name="async",
        description="bounded-staleness (SSP) engine: workers commit "
        "against snapshots at most tau rounds stale over a pluggable "
        "transport (simulated/threaded/multiprocess); tau=0 == distributed",
        needs_mesh=True,
        options_cls=AsyncOptions,
        run=_run_async,
    )
)
