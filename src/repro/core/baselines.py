"""Baselines from the paper's experiments section.

 * STL              -- each task an independent regularized ERM. Realized as
                       DMTRL with Sigma fixed at I/m and no Omega-step
                       (regularizer (lambda m/2)||w_i||^2, exactly the
                       paper's Omega = m I init held fixed).
 * Centralized MTRL -- Zhang & Yeung (2010) alternating optimization run on
                       one machine: full-batch accelerated gradient descent
                       on the primal W-step (+ closed-form Omega-step). The
                       paper's "gold standard".
 * SSDCA            -- single-machine SDCA over ALL dual coordinates with
                       exact (not block-approximated) global updates. The
                       paper's scalable single-machine solution.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import dual as dual_mod
from . import omega as omega_mod
from . import omega_regularizers as omega_reg
from .dmtrl import DMTRLConfig, DMTRLResult, fit as dmtrl_fit
from .losses import get_loss
from .mtl_data import MTLData

Array = jax.Array


# ---------------------------------------------------------------------------
# STL
# ---------------------------------------------------------------------------
def fit_stl(cfg: DMTRLConfig, data: MTLData) -> DMTRLResult:
    return dmtrl_fit(cfg, data, regularizer="identity_stl")


# ---------------------------------------------------------------------------
# Centralized MTRL (primal FISTA W-step + closed-form Omega-step)
# ---------------------------------------------------------------------------
def _primal_grad(data: MTLData, W: Array, omega: Array, lam: float, loss):
    z = jnp.einsum("mnd,md->mn", data.x, W)
    g = loss.subgradient(z, data.y) * data.mask / data.n[:, None].astype(z.dtype)
    grad_emp = jnp.einsum("mn,mnd->md", g, data.x)
    grad_reg = lam * (omega @ W)
    return grad_emp + grad_reg


def fit_centralized_mtrl(
    cfg: DMTRLConfig,
    data: MTLData,
    inner_steps: int = 300,
    lr: float = 0.0,
) -> Tuple[Array, Array, Dict[str, np.ndarray]]:
    """Alternating primal optimization; smooth losses (use smoothed_hinge in
    place of hinge for the central baseline, as subgradient FISTA has no
    guarantee). Returns (W, sigma, history)."""
    loss = get_loss(cfg.loss)
    learns = omega_reg.resolve_regularizer(cfg).learns
    m, d = data.m, data.d
    W = jnp.zeros((m, d), data.x.dtype)
    sigma, omega = omega_mod.init_sigma(m, data.x.dtype)

    # Lipschitz estimate for the gradient: L <= max_i (q_max) + lam*||Omega||;
    # q_max = max row-norm^2 (features), conservative and cheap.
    qmax = float(jnp.max(jnp.sum(data.x**2, axis=-1)))

    hist = {"outer": [], "primal": []}
    for p in range(cfg.outer_iters):
        om_norm = float(jnp.linalg.norm(omega, 2))
        L = qmax + cfg.lam * om_norm
        step = lr if lr > 0 else 1.0 / max(L, 1e-12)

        @jax.jit
        def fista(W):
            def body(carry, _):
                Wk, Vk, tk = carry
                g = _primal_grad(data, Vk, omega, cfg.lam, loss)
                Wn = Vk - step * g
                tn = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * tk**2))
                Vn = Wn + ((tk - 1.0) / tn) * (Wn - Wk)
                return (Wn, Vn, tn), None

            (Wn, _, _), _ = jax.lax.scan(
                body, (W, W, jnp.float32(1.0)), None, length=inner_steps
            )
            return Wn

        W = fista(W)
        hist["outer"].append(p)
        hist["primal"].append(
            float(dual_mod.primal_objective(data, W, omega, cfg.lam, loss))
        )
        if learns:
            sigma, omega = omega_mod.omega_step(W, cfg.omega_jitter)
    return W, sigma, {k: np.asarray(v) for k, v in hist.items()}


# ---------------------------------------------------------------------------
# Single-machine SDCA (exact global coordinate updates over all tasks)
# ---------------------------------------------------------------------------
def fit_ssdca(
    cfg: DMTRLConfig,
    data: MTLData,
    passes: int | None = None,
    track_every_pass: bool = True,
) -> Tuple[Array, Array, Dict[str, np.ndarray]]:
    """SDCA over all n = sum n_i coordinates with exact updates.

    For a sampled coordinate (i, j):
        c = w_i(alpha)^T x_j^i          (exact current margin)
        a = sigma_ii ||x_j||^2 / (lam n_i)
    and the same per-loss closed-form delta as Local SDCA. B (d, m) is
    maintained incrementally; w_i = (1/lam) B sigma[:, i].

    One "pass" = n_max coordinate updates per task (m * n_max total),
    comparable compute to one DMTRL round with H = n_max. Omega-steps happen
    every cfg.rounds passes to mirror Algorithm 1's schedule.
    """
    loss = get_loss(cfg.loss)
    learns = omega_reg.resolve_regularizer(cfg).learns
    m, n_max, d = data.m, data.n_max, data.d
    passes = passes if passes is not None else cfg.outer_iters * cfg.rounds
    alpha = jnp.zeros((m, n_max), data.x.dtype)
    B = jnp.zeros((d, m), data.x.dtype)
    sigma, omega = omega_mod.init_sigma(m, data.x.dtype)
    key = jax.random.PRNGKey(cfg.seed + 17)

    steps_per_pass = m * n_max

    def make_pass(sigma):
        @jax.jit
        def one_pass(alpha, B, key):
            ki, kj = jax.random.split(key)
            tis = jax.random.randint(ki, (steps_per_pass,), 0, m)
            us = jax.random.uniform(kj, (steps_per_pass,))

            def body(h, carry):
                alpha, B = carry
                i = tis[h]
                ni = data.n[i]
                j = jnp.minimum((us[h] * ni.astype(us.dtype)).astype(jnp.int32), ni - 1)
                xj = data.x[i, j]
                nif = ni.astype(xj.dtype)
                sii = sigma[i, i]
                w_i = (B @ sigma[:, i]) / cfg.lam
                c = jnp.dot(xj, w_i)
                a = sii * jnp.dot(xj, xj) / (cfg.lam * nif)
                atilde = alpha[i, j]
                delta = loss.sdca_delta(atilde, c, a, data.y[i, j])
                alpha = alpha.at[i, j].add(delta)
                B = B.at[:, i].add(delta * xj / nif)
                return alpha, B

            return jax.lax.fori_loop(0, steps_per_pass, body, (alpha, B))

        return one_pass

    hist = {"pass": [], "dual": [], "primal": [], "gap": []}
    one_pass = make_pass(sigma)
    for t in range(passes):
        key, sub = jax.random.split(key)
        alpha, B = one_pass(alpha, B, sub)
        if track_every_pass:
            dd = dual_mod.dual_objective(data, alpha, sigma, cfg.lam, loss)
            pp = dual_mod.primal_objective_from_alpha(data, alpha, sigma, cfg.lam, loss)
            hist["pass"].append(t + 1)
            hist["dual"].append(float(dd))
            hist["primal"].append(float(pp))
            hist["gap"].append(float(pp - dd))
        if learns and (t + 1) % cfg.rounds == 0:
            W = (B @ sigma).T / cfg.lam
            sigma, omega = omega_mod.omega_step(W, cfg.omega_jitter)
            one_pass = make_pass(sigma)

    W = (B @ sigma).T / cfg.lam
    return W, sigma, {k: np.asarray(v) for k, v in hist.items()}
