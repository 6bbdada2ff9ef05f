"""Plain reference of DMTRL's Algorithm 1 (Liu, Pan & Ho, KDD 2017).

Written from the paper, importing nothing of the program under test:

    Sigma = I/m, alpha = 0, W = 0
    for p in 1..P:
        rho = eta * max_i sum_i' |sigma_ii'| / sigma_ii        (Lemma 10)
        for t in 1..T:                                           (W-step)
            each task i: H coordinate steps of Algorithm 2, one at a time:
                c = w_i.x_j + kappa x_j.r,  a = kappa |x_j|^2,
                kappa = rho sigma_ii / (lambda n_i)
                delta = argmax of the scalar dual (closed form per loss)
                dalpha_j += delta,  r += delta x_j
            alpha += eta dalpha;  db_i = eta r_i / n_i
            W += Sigma dB / lambda                               (server)
            record P(W(alpha)) and D(alpha)
        Sigma = (W W^T)^(1/2) / tr(...), jittered, trace 1       (Omega-step)
        W = W(alpha) = Sigma B / lambda,  b_i = X_i^T alpha_i / n_i

Coordinates are drawn as the program's engines draw them, so that the
iterates can be compared one for one: the key schedule of Algorithm 1
(split per outer iteration, split per round, folded with the task index
and the sample-partition index 0) and H uniforms per task mapped to
min(floor(u n_i), n_i - 1). The W-step runs in ``dtype`` at the highest
matmul precision; the Omega-step and rho run in float64 on the host.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _delta(loss: str, at, c, a, y):
    if loss == "hinge":
        a = jnp.maximum(a, 1e-12)
        return y * jnp.clip(y * (at + (y - c) / a), 0.0, 1.0) - at
    if loss == "squared":
        return (y - c - at) / (1.0 + a)
    raise ValueError(f"the reference has no closed form for loss {loss!r}")


def _loss(loss: str, z, y):
    if loss == "hinge":
        return jnp.maximum(0.0, 1.0 - y * z)
    return 0.5 * (z - y) ** 2


def _conj_at_minus(loss: str, alpha, y):
    """l*(-alpha, y)."""
    if loss == "hinge":
        return -alpha * y
    return 0.5 * alpha**2 - alpha * y


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _round(loss, lam, eta, H, x, y, n, alpha, W, sigma, key, rho):
    m = x.shape[0]
    keys = jax.vmap(lambda t: jax.random.fold_in(jax.random.fold_in(key, t), 0))(
        jnp.arange(m, dtype=jnp.int32)
    )
    dt = x.dtype

    def task(xi, yi, ai, wi, ni, sii, ki):
        u = jax.random.uniform(ki, (H,))
        coords = jnp.minimum((u * ni.astype(u.dtype)).astype(jnp.int32), ni - 1)
        kappa = (rho * sii / (lam * jnp.maximum(ni, 1).astype(dt))).astype(dt)

        def body(h, carry):
            da, r = carry
            j = coords[h]
            xj = xi[j]
            c = jnp.dot(xj, wi, precision=HIGHEST) + kappa * jnp.dot(
                xj, r, precision=HIGHEST
            )
            a = kappa * jnp.dot(xj, xj, precision=HIGHEST)
            d = _delta(loss, ai[j] + da[j], c, a, yi[j]).astype(dt)
            return da.at[j].add(d), r + d * xj

        return jax.lax.fori_loop(0, H, body, (jnp.zeros_like(ai), jnp.zeros_like(wi)))

    da, r = jax.vmap(task)(x, y, alpha, W, n, jnp.diagonal(sigma), keys)
    alpha = alpha + eta * da
    db = (eta * r / jnp.maximum(n, 1)[:, None].astype(dt)).astype(dt)
    W = W + (jnp.matmul(sigma, db, precision=HIGHEST) / lam).astype(dt)
    return alpha, W


@functools.partial(jax.jit, static_argnums=(0, 1))
def _objectives(loss, lam, x, y, mask, n, alpha, sigma):
    """(primal, dual) at W(alpha), and W(alpha)."""
    nf = jnp.maximum(n, 1).astype(x.dtype)
    B = jnp.einsum("mnd,mn->md", x, alpha * mask, precision=HIGHEST) / nf[:, None]
    quad = jnp.sum(sigma * jnp.matmul(B, B.T, precision=HIGHEST))
    W = jnp.matmul(sigma, B, precision=HIGHEST) / lam
    z = jnp.einsum("mnd,md->mn", x, W, precision=HIGHEST)
    emp = jnp.sum(_loss(loss, z, y) * mask / nf[:, None])
    conj = jnp.sum(_conj_at_minus(loss, alpha, y) * mask / nf[:, None])
    return emp + quad / (2.0 * lam), -quad / (2.0 * lam) - conj, W


def omega_step(W, jitter: float) -> np.ndarray:
    W = np.asarray(W, np.float64)
    m = W.shape[0]
    M = W @ W.T
    ev, V = np.linalg.eigh(0.5 * (M + M.T))
    s = np.sqrt(np.maximum(ev, 0.0))
    s = s / s.sum() if s.sum() > 1e-30 else np.full(m, 1.0 / m)
    s = s + jitter
    s = s / s.sum()
    sigma = (V * s) @ V.T
    return 0.5 * (sigma + sigma.T)


def rho_lemma10(sigma, eta: float) -> float:
    sigma = np.asarray(sigma, np.float64)
    dd = np.maximum(np.diag(sigma), 1e-30)
    return float(eta * np.max(np.abs(sigma).sum(axis=1) / dd))


def fit(x, y, mask, n, *, loss, lam, eta, outer_iters, rounds, H, seed,
        jitter=1e-6, dtype=jnp.float32):
    """Algorithm 1 on (m, n_max, d) arrays. Returns a dict with the final
    W, alpha and Sigma, and the primal and dual objective after every
    round (before that outer iteration's Omega-step)."""
    x, y, mask = (jnp.asarray(a, dtype) for a in (x, y, mask))
    n = jnp.asarray(n, jnp.int32)
    m, n_max, d = x.shape
    alpha = jnp.zeros((m, n_max), dtype)
    W = jnp.zeros((m, d), dtype)
    sigma = np.eye(m) / m
    key = jax.random.PRNGKey(seed)
    primal, dual = [], []
    for _ in range(outer_iters):
        rho = rho_lemma10(sigma, eta)
        sig = jnp.asarray(sigma, dtype)
        key, outer_key = jax.random.split(key)
        round_keys = jax.random.split(outer_key, rounds)
        for t in range(rounds):
            alpha, W = _round(
                loss, lam, eta, H, x, y, n, alpha, W, sig, round_keys[t],
                jnp.asarray(rho, dtype),
            )
            p, dd, _ = _objectives(loss, lam, x, y, mask, n, alpha, sig)
            primal.append(float(p))
            dual.append(float(dd))
        sigma = omega_step(np.asarray(W, np.float64), jitter)
        _, _, W = _objectives(
            loss, lam, x, y, mask, n, alpha, jnp.asarray(sigma, dtype)
        )
    return {
        "W": np.asarray(W, np.float64),
        "alpha": np.asarray(alpha, np.float64),
        "sigma": sigma,
        "primal": np.asarray(primal),
        "dual": np.asarray(dual),
    }
