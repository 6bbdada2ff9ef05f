"""DMTRL Algorithm 1 — single-process reference driver.

Implements the alternating procedure exactly as in the paper:

  for p in 1..P:                      (alternating iterations)
    for t in 1..T:                    (W-step rounds == communication rounds)
      for each task i in parallel:    (vmap == the paper's workers)
        dalpha_[i] <- LocalSDCA(alpha_[i], w_i, sigma_ii)     (H inner iters)
        alpha_[i] += eta * dalpha_[i]
        delta_b_i  = (eta/n_i) X_i^T dalpha_[i]
      server: w_i += (1/lambda) sum_i' delta_b_i' sigma_ii'   (the reduce)
    server: Sigma, Omega <- omega_step(W); broadcast sigma rows
    rho <- Lemma-10 bound on the new Sigma (paper Section 7.1)

The distributed (shard_map) version in ``distributed.py`` reuses the same
per-round math; this module is the semantic oracle it is tested against.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import dual as dual_mod
from . import omega_regularizers as omega_reg
from . import sigma_view as sigma_view_mod
from ..obs.metrics import get_registry
from .losses import get_loss
from .mtl_data import MTLData, PackedMTLData, refuse_packed
from .sigma_view import SigmaView
from .solver_backends import get_backend

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class DMTRLConfig:
    """The algorithm's settings, shared by every engine.

    How a fit runs is not set here: the mesh engines take ``mesh`` and
    ``axes``, and the async engine its ``AsyncOptions``
    (core/async_dmtrl.py).
    """

    loss: str = "hinge"
    lam: float = 1e-3  # lambda in Eq. (1)
    eta: float = 1.0  # aggregation parameter (paper uses 1.0)
    outer_iters: int = 5  # P
    rounds: int = 20  # T (communication rounds per W-step)
    local_iters: int = 0  # H; 0 => n_max (one local epoch per round)
    solver: str = "block_gram"  # local-SDCA backend name, resolved through
    #               core.solver_backends: "naive" | "block_gram" |
    #               "pallas_block" | "pallas_round"
    block_size: int = 64
    rho_mode: str = "lemma10"  # "lemma10" | "spectral" | "fixed"
    rho_fixed: float = 1.0
    omega_jitter: float = 1e-6
    omega_regularizer: str = "trace_constraint"  # family member name,
    #               resolved through core.omega_regularizers
    #               ("identity_stl" holds Sigma at I/m)
    seed: int = 0
    track_every: int = 1  # record objectives every k rounds

    def __post_init__(self):
        if self.omega_regularizer not in omega_reg.available_regularizers():
            raise ValueError(
                f"unknown omega_regularizer {self.omega_regularizer!r}; "
                f"have {sorted(omega_reg.available_regularizers())}"
            )


@dataclasses.dataclass(frozen=True)
class WarmStart:
    """Prior state to continue training from (estimator.partial_fit).

    ``alpha``: (m, n_max) dual variables, ``sigma``/``omega``: (m, m) task
    covariance/precision — all at the RAW (unpadded) problem size. W is
    always rederived as W(alpha) under sigma, never carried separately.
    Structured runs may carry a SigmaView for ``sigma`` and None (or a
    view) for ``omega``.
    """

    alpha: Array
    sigma: Array
    omega: Optional[Array] = None


@dataclasses.dataclass
class DMTRLResult:
    W: Array  # (m, d)
    alpha: Array  # (m, n_max)
    sigma: Array  # (m, m) dense, or a SigmaView when m is huge
    omega: Optional[Array]  # (m, m); None for structured members w/o inverse
    history: Dict[str, np.ndarray]
    rho_per_outer: List[float]
    # the structured representation itself, when the run used one
    sigma_view: Optional[SigmaView] = None


def _rho_value(
    cfg: DMTRLConfig,
    sigma: Array,
    n_blocks_scale: float = 1.0,
    reg: Optional[omega_reg.OmegaRegularizer] = None,
) -> float:
    """rho safety bound for the current Sigma, via the regularizer family
    (every member supplies its bound; the default is the paper's)."""
    if reg is None:
        reg = omega_reg.resolve_regularizer(cfg)
    rho = reg.rho(sigma, cfg.eta, cfg.rho_mode, cfg.rho_fixed)
    if cfg.rho_mode == "fixed":
        return float(rho)
    return float(rho) * n_blocks_scale


def make_w_step_round(cfg: DMTRLConfig, n_max: int, rho: float):
    """One communication round: local updates (vmap over tasks) + reduce.

    Returns round(data, alpha, W, sigma, key) -> (alpha, W). jit-able; the
    data is an argument, so a jitted round does not embed it as a constant.
    """
    loss = get_loss(cfg.loss)
    backend = get_backend(cfg.solver)
    H = backend.round_local_iters(cfg.local_iters or n_max, cfg.block_size)
    solver = backend.make(loss, rho, cfg.lam, H, block=cfg.block_size)

    def round_fn(data, alpha, W, sigma, key):
        # same per-(task, pod=0) key derivation as distributed.py so the
        # single-process reference and the mesh version produce bit-equal
        # coordinate samples (tested).
        tids = jnp.arange(data.m, dtype=jnp.int32)
        keys = jax.vmap(
            lambda t: jax.random.fold_in(jax.random.fold_in(key, t), 0)
        )(tids)
        if isinstance(sigma, SigmaView):
            sigma_diag = sigma.diag()
        else:
            sigma_diag = jnp.diag(sigma)
        dalpha, r = jax.vmap(solver)(
            data.x, data.y, alpha, W, data.n, sigma_diag, keys
        )
        alpha = alpha + cfg.eta * dalpha
        # delta_b rows: (m, d); server reduce: W += (1/lam) Sigma @ dB
        db = cfg.eta * r / data.n[:, None].astype(r.dtype)
        if isinstance(sigma, SigmaView):
            W = W + sigma.matvec(db) / cfg.lam
        else:
            W = W + (sigma @ db) / cfg.lam
        return alpha, W

    return round_fn


def driver_program(name: str):
    """Memoise a builder of jitted programs on its static key.

    A fresh ``jax.jit`` starts with an empty cache, so a program rebuilt per
    fit (or per outer iteration) is traced and lowered again each time.
    The builder's arguments are the key: everything the program's trace
    reads, and no data, seed or ``rho`` value. Each lookup counts in
    ``repro_engine_driver_programs_total{program, outcome}``, ``outcome``
    ``built`` or ``reused``.
    """

    def wrap(build):
        cached = functools.lru_cache(maxsize=16)(build)

        @functools.wraps(build)
        def lookup(*key):
            misses = cached.cache_info().misses
            program = cached(*key)
            built = cached.cache_info().misses > misses
            get_registry().counter(
                "repro_engine_driver_programs_total",
                "jitted driver programs looked up, by program and outcome",
                labels=("program", "outcome"),
            ).inc(program=name, outcome="built" if built else "reused")
            return program

        lookup.cache_clear = cached.cache_clear
        return lookup

    return wrap


@driver_program("objectives")
def _objectives_program(lam: float, loss_name: str, name: str = "objectives"):
    loss = get_loss(loss_name)

    def objectives(data, alpha, sigma):
        dd = dual_mod.dual_objective(data, alpha, sigma, lam, loss)
        pp = dual_mod.primal_objective_from_alpha(data, alpha, sigma, lam, loss)
        return dd, pp

    objectives.__name__ = objectives.__qualname__ = name  # jit_<name> in the trace
    return jax.jit(objectives)


@driver_program("w_from_alpha")
def _w_from_alpha_program(lam: float, name: str = "w_from_alpha"):
    def w_from_alpha(data, alpha, sigma):
        return dual_mod.weights_from_alpha(data, alpha, sigma, lam)

    w_from_alpha.__name__ = w_from_alpha.__qualname__ = name
    return jax.jit(w_from_alpha)


def make_data_fns(cfg: DMTRLConfig, data: Union[MTLData, PackedMTLData]):
    """Jitted ``objectives(alpha, sigma) -> (dual, primal)`` and
    ``w_from_alpha(alpha, sigma) -> W`` over ``data``.

    The data enters the compiled programs as an argument: an array closed
    over by a jitted function is baked into the program as a constant, and
    at MNIST width ``x`` alone is 376 MB. The programs are shared by every
    fit with the same ``(lam, loss)``, so a refit of same-shaped data
    traces nothing.
    """
    # packed rows get programs of their own names in the device trace
    # (modules jit_packed_objectives, jit_packed_w_from_alpha)
    prefix = "packed_" if data.layout == "packed" else ""
    objectives = _objectives_program(cfg.lam, cfg.loss, prefix + "objectives")
    w_from_alpha = _w_from_alpha_program(cfg.lam, prefix + "w_from_alpha")
    return (
        lambda alpha, sigma: objectives(data, alpha, sigma),
        lambda alpha, sigma: w_from_alpha(data, alpha, sigma),
    )


def w_step(
    cfg: DMTRLConfig,
    data: MTLData,
    alpha: Array,
    W: Array,
    sigma: Array,
    rho: float,
    key: Array,
    track: bool = True,
) -> tuple[Array, Array, Dict[str, np.ndarray]]:
    """Run cfg.rounds communication rounds; returns updated alpha, W, history."""
    round_fn = jax.jit(make_w_step_round(cfg, data.n_max, rho))
    objectives, _ = make_data_fns(cfg, data)

    hist = {"round": [], "dual": [], "primal": [], "gap": []}
    keys = jax.random.split(key, cfg.rounds)
    for t in range(cfg.rounds):
        alpha, W = round_fn(data, alpha, W, sigma, keys[t])
        if track and (t % cfg.track_every == 0 or t == cfg.rounds - 1):
            d, p = objectives(alpha, sigma)
            hist["round"].append(t + 1)
            hist["dual"].append(float(d))
            hist["primal"].append(float(p))
            hist["gap"].append(float(p - d))
    return alpha, W, {k: np.asarray(v) for k, v in hist.items()}


def fit(
    cfg: DMTRLConfig,
    data: MTLData,
    track: bool = True,
    *,
    init: Optional[WarmStart] = None,
    regularizer=None,
) -> DMTRLResult:
    """Full Algorithm 1: P alternations of (W-step, Omega-step).

    ``init`` warm-starts from a prior (alpha, sigma, omega) — W is rederived
    as W(alpha); ``regularizer`` overrides the Omega family member resolved
    from the config (an ``OmegaRegularizer`` instance or name).
    """
    refuse_packed(data, "the reference engine")
    reg = omega_reg.resolve_regularizer(cfg, regularizer, m=data.m)
    key = jax.random.PRNGKey(cfg.seed)
    m, n_max = data.m, data.n_max
    if init is not None:
        alpha = jnp.asarray(init.alpha, data.x.dtype)
        if isinstance(init.sigma, SigmaView):
            sigma = init.sigma
        else:
            sigma = jnp.asarray(init.sigma, data.x.dtype)
        omega = init.omega
        if omega is not None and not isinstance(omega, SigmaView):
            omega = jnp.asarray(omega, data.x.dtype)
        W = dual_mod.weights_from_alpha(data, alpha, sigma, cfg.lam)
    else:
        alpha = jnp.zeros((m, n_max), data.x.dtype)
        W = jnp.zeros((m, data.d), data.x.dtype)
        sigma, omega = reg.init(m, data.x.dtype)

    history: Dict[str, List[np.ndarray]] = {
        "round": [],
        "dual": [],
        "primal": [],
        "gap": [],
        "outer": [],
    }
    rhos: List[float] = []
    rounds_seen = 0
    for p in range(cfg.outer_iters):
        rho = _rho_value(cfg, sigma, reg=reg)
        rhos.append(rho)
        key, sub = jax.random.split(key)
        alpha, W, hist = w_step(cfg, data, alpha, W, sigma, rho, sub, track=track)
        if track:
            history["round"].append(hist["round"] + rounds_seen)
            history["dual"].append(hist["dual"])
            history["primal"].append(hist["primal"])
            history["gap"].append(hist["gap"])
            history["outer"].append(np.full_like(hist["round"], p))
        rounds_seen += cfg.rounds
        if reg.learns:
            # Algorithm 1 row 11 runs after every W-step, including the last.
            sigma, omega = reg.step(W, cfg.omega_jitter)
            # Sigma changed => the dual problem (K) changed; W(alpha) must be
            # recomputed under the new Sigma (B is Sigma-independent).
            W = dual_mod.weights_from_alpha(data, alpha, sigma, cfg.lam)

    hist_np = {
        k: (np.concatenate(v) if v else np.zeros((0,))) for k, v in history.items()
    }
    sigma_out, omega_out, sv = sigma_view_mod.result_sigma_omega(sigma, omega)
    return DMTRLResult(
        W=W,
        alpha=alpha,
        sigma=sigma_out,
        omega=omega_out,
        history=hist_np,
        rho_per_outer=rhos,
        sigma_view=sv,
    )
