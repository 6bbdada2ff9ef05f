"""Asynchronous bounded-staleness DMTRL engine — a thin protocol driver.

Architecture (post transport refactor)
--------------------------------------
The paper's Algorithm 1 is bulk-synchronous: every communication round
barriers on ``all_gather(delta_b)`` before the server reduce, so one
straggler worker stalls all m tasks. Baytas et al. (arXiv:1609.09563) and
Wang et al. (arXiv:1802.03830) show the same primal-dual MTL structure
tolerates *bounded staleness* in the worker->server updates. The portable
object is the PROTOCOL — snapshot -> local solve -> SSP-gated commit —
not the execution substrate, so this module is now only the outer
alternation:

    for p in outer_iters:
        rho  <- regularizer rho bound on the (possibly pending) Sigma
        transport.run_w_step(p, rho, outer_key)      # R protocol rounds
        Sigma, Omega <- regularizer.step(W)          # Omega-step
        transport.install_sigma(...)                 # maybe overlapped

over a pluggable ``core.transport`` member (``AsyncOptions.transport``;
every staleness knob below is a field of ``AsyncOptions``):

  simulated     deterministic per-worker clock simulation, fused masked
                SPMD commits — bit-reproducible; the default and the
                bit-parity anchor (tau=0 == ``fit_distributed`` exactly).
  threaded      real in-host parameter server (G worker threads, lock-
                protected versioned state, nondeterministic arrivals).
  multiprocess  socket/pickle parameter server with per-worker processes.

Staleness semantics (all transports)
------------------------------------
A contribution's *staleness* is the number of server commit events between
its snapshot and its application; its *lag* is how many rounds ahead of the
slowest worker it ran. The SSP gate admits a worker to round r only while
``r <= min_completed + tau`` (``tau=0`` degenerates to the bulk-synchronous
barrier). Every applied contribution flows through one accounting path —
``transport.CommitReceipt -> record_receipt -> history`` — summarized by
``convergence.staleness_summary`` / ``convergence.effective_gap_curve``
(``w_worker / w_round / w_staleness / w_lag / w_tick`` + ``tau_trace`` /
``gate_refusals`` in the returned history).

``tau="auto"`` turns the static bound into a small online controller
(``transport._adapt_tau``): widen on gate-refusal episodes, narrow when the
observed lag never used the slack — and, when ``staleness_budget`` is set,
narrow whenever the windowed mean commit staleness exceeds the budget even
if the gate never refused (cost-aware mode). The bound in effect at every
commit is recorded in ``history["tau_trace"]``.

The Omega-step overlaps with in-flight W-rounds instead of barriering:
with ``omega_delay = k > 0`` the Sigma/Omega computed at a W-step boundary
is *installed* only after k server commits of the next W-step; rounds
started inside that window read the stale Sigma through their snapshot.
rho is still computed from the new Sigma at the boundary. A pending Sigma
is never dropped — it lands at the next barrier at the latest.

Parity anchors: at ``tau=0`` the ``simulated`` transport reproduces
``fit_distributed``'s ``(alpha, W)`` iterates bit-exactly (tested on 1- and
8-device meshes) and its integer event bookkeeping is pinned by golden
histories (``tests/golden/``); ``threaded``/``multiprocess`` match the
``reference`` engine at ``tau=0`` to numerical tolerance (commit order
within a barriered round is nondeterministic, so float association
differs).
"""
from __future__ import annotations

import dataclasses
import numbers
from typing import Optional, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh

from . import omega_regularizers as omega_reg
from .distributed import MeshAxes
from .dmtrl import DMTRLConfig, WarmStart, _rho_value
from .mtl_data import MTLData, refuse_packed
from .transport import (  # re-exported for backward compatibility
    _adapt_tau,
    _worker_delays,
    get_transport,
    make_async_tick,
)
from .wire import available_codecs
from ..obs.metrics import publish_wire_stats
from ..obs.trace import span

Array = jax.Array

__all__ = [
    "AsyncOptions",
    "fit_async",
    "make_async_tick",
    "_adapt_tau",
    "_worker_delays",
]


def _validate_tau(tau) -> None:
    if tau == "auto":
        return
    if not isinstance(tau, int) or isinstance(tau, bool):
        raise ValueError(f'tau must be an int >= 0 or "auto", got {tau!r}')
    if tau < 0:
        raise ValueError(f"tau must be >= 0, got {tau}")


def _validate_topology(topology) -> None:
    """Named topologies are checked against the known set; an explicit
    adjacency must be a square symmetric 0/1 matrix (connectivity is
    checked at transport setup, where the worker count is known)."""
    if isinstance(topology, str):
        if topology not in ("ring", "torus", "complete"):
            raise ValueError(
                f"topology must be 'ring' | 'torus' | 'complete' or an "
                f"explicit adjacency matrix, got {topology!r}"
            )
        return
    adj = np.asarray(topology)
    if adj.ndim != 2 or adj.shape[0] != adj.shape[1] or adj.shape[0] < 1:
        raise ValueError(
            f"adjacency topology must be a square matrix, got shape "
            f"{adj.shape}"
        )
    if not np.array_equal(adj, adj.T):
        raise ValueError("adjacency topology must be symmetric")
    if not np.isin(adj, (0, 1)).all():
        raise ValueError("adjacency topology entries must be 0/1")


def validate_async_fields(opts: "AsyncOptions") -> None:
    """Eager validation of the async knobs, so that a malformed value
    (e.g. tau="fast") raises at construction, not mid-fit."""
    _validate_tau(opts.tau)
    if not isinstance(opts.transport, str):
        raise ValueError(
            f"transport must be a core.transport member name, got "
            f"{opts.transport!r}"
        )
    _validate_topology(opts.topology)
    if not isinstance(opts.codec, str):
        raise ValueError(
            f"codec must be a core.wire codec name, got {opts.codec!r}"
        )
    if opts.codec not in available_codecs():
        raise ValueError(
            f"unknown wire codec {opts.codec!r}; have "
            f"{sorted(available_codecs())}"
        )
    n_workers, budget = opts.n_workers, opts.staleness_budget
    if n_workers is not None and (
        not isinstance(n_workers, numbers.Integral)
        or isinstance(n_workers, bool)
        or n_workers < 1
    ):
        raise ValueError(f"n_workers must be an int >= 1 or None, got {n_workers!r}")
    if budget is not None and (
        isinstance(budget, bool)
        or not isinstance(budget, numbers.Real)
        or budget < 0
    ):
        raise ValueError(
            f"staleness_budget must be a float >= 0 or None, got {budget!r}"
        )
    if budget is not None and opts.tau != "auto":
        raise ValueError(
            f'staleness_budget only drives the tau="auto" controller; it '
            f"would be silently ignored with tau={opts.tau!r}"
        )
    tau_max = opts.tau_max
    if not isinstance(tau_max, int) or isinstance(tau_max, bool) or tau_max < 0:
        raise ValueError(f"tau_max must be an int >= 0, got {tau_max!r}")
    omega_delay = opts.omega_delay
    if (
        not isinstance(omega_delay, int)
        or isinstance(omega_delay, bool)
        or omega_delay < 0
    ):
        raise ValueError(f"omega_delay must be an int >= 0, got {omega_delay!r}")
    if opts.async_delays is not None:
        # numbers.Integral admits numpy ints (delay schedules are often
        # built from numpy arrays); _worker_delays coerces them with int()
        bad = [
            v
            for v in opts.async_delays
            if not isinstance(v, numbers.Integral)
            or isinstance(v, bool)
            or v < 1
        ]
        if bad:
            raise ValueError(
                f"async_delays entries must be ints >= 1, got "
                f"{opts.async_delays!r}"
            )


@dataclasses.dataclass(frozen=True)
class AsyncOptions:
    """Staleness knobs of the async engine: the one home of ``tau`` and
    its friends, read by the transports (``Transport.setup``).

    Validation is eager: ``AsyncOptions(tau="fast")`` raises at
    construction with a clear message, not mid-fit.

    Transport selection (``core.transport`` registry): ``transport`` names
    the execution substrate of the snapshot/commit protocol; ``n_workers``
    sets the worker count for the host transports (``threaded`` /
    ``multiprocess``), which otherwise fall back to the mesh data-axis
    size (``simulated`` always derives workers from the mesh).
    """

    tau: Union[int, str] = 0  # SSP staleness bound: a worker may run at
    #               most tau rounds ahead of the slowest worker (0 == bulk-
    #               synchronous); "auto" adapts the bound online from the
    #               observed staleness histogram (transport._adapt_tau)
    tau_max: int = 8  # clamp for the tau="auto" controller
    async_delays: Optional[Tuple[int, ...]] = None  # simulated per-worker
    #               solve ticks; None == homogeneous workers (host
    #               transports turn them into sleep pacing)
    omega_delay: int = 0  # server commits the Sigma install may lag behind
    #               (0 == barrier, same as sync)
    transport: str = "simulated"  # core.transport member name
    n_workers: Optional[int] = None  # host-transport worker count
    staleness_budget: Optional[float] = None  # tau="auto" cost target:
    #               narrow when windowed mean commit staleness exceeds it
    topology: Union[str, tuple] = "complete"  # gossip neighbor graph
    #               ("ring" | "torus" | "complete" | explicit adjacency)
    codec: str = "none"  # wire codec for the (delta_w, Sigma) messages
    #               ("none" | "bf16" | "int8"; core.wire registry)

    def __post_init__(self):
        validate_async_fields(self)


def fit_async(
    cfg: DMTRLConfig,
    raw: MTLData,
    mesh: Optional[Mesh] = None,
    axes: Optional[MeshAxes] = None,
    track: bool = True,
    *,
    options: Optional[AsyncOptions] = None,
    init: Optional[WarmStart] = None,
    regularizer=None,
):
    """Algorithm 1 under the bounded-staleness execution model.

    Same signature/returns as ``fit_distributed``: (W, sigma, state, hist).
    The history additionally carries per-commit staleness events and the
    transport clock of every objective sample.

    ``options`` (AsyncOptions, its defaults when None) holds the staleness
    knobs, ``transport=`` among them, which picks the execution substrate;
    ``init`` warm-starts from raw-shaped (alpha, sigma, omega);
    ``regularizer`` overrides the Omega family member. ``mesh`` is required
    by the ``simulated`` transport and optional for the host transports
    (they only read its data-axis size when ``n_workers`` is unset).
    """
    refuse_packed(raw, "the async engine")
    if axes is None:
        axes = MeshAxes()
    opts = options or AsyncOptions()
    reg = omega_reg.resolve_regularizer(cfg, regularizer, m=raw.m)
    # root span + sequential driver-phase spans: "setup" / per-outer
    # "w_step" / "omega_step" / "result" tile "fit_async" almost exactly,
    # which is what bench_obs's breakdown-sums-to-total check leans on
    with span("fit_async", cat="driver", transport=opts.transport):
        with span("setup", cat="driver", transport=opts.transport):
            spec = get_transport(opts.transport)
            transport = spec.factory()
            transport.setup(
                cfg, raw, mesh=mesh, axes=axes, reg=reg, init=init,
                track=track, options=opts,
            )
        key = jax.random.PRNGKey(cfg.seed)
        # rho always sees the NEWEST Sigma, installed or pending (a pending
        # install is a worker-visibility delay, not a safety-bound delay)
        rho_sigma = transport.rho_sigma()
        try:
            for p in range(cfg.outer_iters):
                rho = _rho_value(
                    cfg, rho_sigma, n_blocks_scale=float(transport.n_pods), reg=reg
                )
                key, outer_key = jax.random.split(key)
                with span("w_step", cat="driver", outer=p):
                    transport.run_w_step(p, rho, outer_key)
                if reg.learns:
                    with span("omega_step", cat="driver", outer=p):
                        sigma_t, omega_t = reg.step(
                            transport.w_true(), cfg.omega_jitter
                        )
                        sig, om = transport.pad_sigma(sigma_t, omega_t)
                        # overlapped Omega-step: defer the install into the
                        # next W-step except at the end (the last Sigma must
                        # land now)
                        defer = opts.omega_delay > 0 and p < cfg.outer_iters - 1
                        transport.install_sigma(sig, om, defer=defer)
                        rho_sigma = sig
            with span("result", cat="driver", transport=opts.transport):
                out = transport.result()
                ws = getattr(transport, "wire_stats", None)
                if ws is not None:
                    publish_wire_stats(ws, transport=opts.transport)
            return out
        finally:
            transport.close()
