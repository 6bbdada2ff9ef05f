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

over a pluggable ``core.transport`` member (``AsyncOptions.transport``):

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
from typing import Optional, Tuple, Union

import jax
from jax.sharding import Mesh

from . import omega_regularizers as omega_reg
from .distributed import MeshAxes
from .dmtrl import DMTRLConfig, WarmStart, _rho_value, validate_async_fields
from .mtl_data import MTLData, refuse_packed
from .transport import (  # re-exported for backward compatibility
    _adapt_tau,
    _worker_delays,
    get_transport,
    make_async_tick,
)
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


@dataclasses.dataclass(frozen=True)
class AsyncOptions:
    """Staleness knobs of the async engine, split out of the legacy
    kitchen-sink config (the new home of ``DMTRLConfig.tau`` & friends).

    Validation is eager: ``AsyncOptions(tau="fast")`` raises at
    construction with a clear message, not mid-fit.

    Transport selection (``core.transport`` registry): ``transport`` names
    the execution substrate of the snapshot/commit protocol; ``n_workers``
    sets the worker count for the host transports (``threaded`` /
    ``multiprocess``), which otherwise fall back to the mesh data-axis
    size (``simulated`` always derives workers from the mesh).
    """

    tau: Union[int, str] = 0  # SSP staleness bound; "auto" adapts online
    tau_max: int = 8  # clamp for the tau="auto" controller
    async_delays: Optional[Tuple[int, ...]] = None  # simulated per-worker
    #               solve ticks; None == homogeneous workers (host
    #               transports turn them into sleep pacing)
    omega_delay: int = 0  # server commits the Sigma install may lag behind
    transport: str = "simulated"  # core.transport member name
    n_workers: Optional[int] = None  # host-transport worker count
    staleness_budget: Optional[float] = None  # tau="auto" cost target:
    #               narrow when windowed mean commit staleness exceeds it
    topology: Union[str, tuple] = "complete"  # gossip neighbor graph
    #               ("ring" | "torus" | "complete" | explicit adjacency)
    codec: str = "none"  # wire codec for the (delta_w, Sigma) messages
    #               ("none" | "bf16" | "int8"; core.wire registry)

    def __post_init__(self):
        validate_async_fields(
            self.tau,
            self.tau_max,
            self.async_delays,
            self.omega_delay,
            transport=self.transport,
            n_workers=self.n_workers,
            staleness_budget=self.staleness_budget,
            topology=self.topology,
            codec=self.codec,
        )

    def merge_into(self, cfg: DMTRLConfig) -> DMTRLConfig:
        return dataclasses.replace(
            cfg,
            tau=self.tau,
            tau_max=self.tau_max,
            async_delays=self.async_delays,
            omega_delay=self.omega_delay,
            transport=self.transport,
            n_workers=self.n_workers,
            staleness_budget=self.staleness_budget,
            topology=self.topology,
            codec=self.codec,
        )


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

    ``options`` (AsyncOptions) overrides the legacy staleness fields of the
    config — including ``transport=`` which picks the execution substrate;
    ``init`` warm-starts from raw-shaped (alpha, sigma, omega);
    ``regularizer`` overrides the Omega family member. ``mesh`` is required
    by the ``simulated`` transport and optional for the host transports
    (they only read its data-axis size when ``n_workers`` is unset).
    """
    refuse_packed(raw, "the async engine")
    if axes is None:
        axes = MeshAxes()
    if options is not None:
        cfg = options.merge_into(cfg)
    # cfg may predate the eager __post_init__ validation (e.g. built via
    # dataclasses.replace on old pickles); keep the fit-time check too.
    validate_async_fields(
        cfg.tau,
        cfg.tau_max,
        cfg.async_delays,
        cfg.omega_delay,
        transport=cfg.transport,
        n_workers=cfg.n_workers,
        staleness_budget=cfg.staleness_budget,
        topology=getattr(cfg, "topology", "complete"),
        codec=getattr(cfg, "codec", "none"),
    )
    reg = omega_reg.resolve_regularizer(cfg, regularizer, m=raw.m)
    # root span + sequential driver-phase spans: "setup" / per-outer
    # "w_step" / "omega_step" / "result" tile "fit_async" almost exactly,
    # which is what bench_obs's breakdown-sums-to-total check leans on
    with span("fit_async", cat="driver", transport=cfg.transport):
        with span("setup", cat="driver", transport=cfg.transport):
            spec = get_transport(cfg.transport)
            transport = spec.factory()
            transport.setup(
                cfg, raw, mesh=mesh, axes=axes, reg=reg, init=init, track=track
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
                        defer = cfg.omega_delay > 0 and p < cfg.outer_iters - 1
                        transport.install_sigma(sig, om, defer=defer)
                        rho_sigma = sig
            with span("result", cat="driver", transport=cfg.transport):
                out = transport.result()
                ws = getattr(transport, "wire_stats", None)
                if ws is not None:
                    publish_wire_stats(ws, transport=cfg.transport)
            return out
        finally:
            transport.close()
