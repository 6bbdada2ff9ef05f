"""Dual/primal objectives, the primal-dual map W(alpha), and the duality gap.

Notation (paper Thm. 1):
    b_i        = (1/n_i) X_i^T alpha_[i]                      (d,)
    B          = [b_1 ... b_m]                                (d, m)
    w_i(alpha) = (1/lambda) sum_i' b_i' sigma_ii'  =>  W = (1/lambda) B Sigma
    alpha^T K alpha = tr(Sigma B^T B)
    D(alpha) = -(1/2 lambda) tr(Sigma B^T B) - sum_i (1/n_i) sum_j l*(-alpha_j^i)
    P(W)     = sum_i (1/n_i) sum_j l(w_i^T x_j^i) + (lambda/2) tr(W Omega W^T)

For W = W(alpha) the regularizer simplifies:
    tr(W Omega W^T) = (1/lambda^2) tr(Sigma B^T B)     (since Sigma Omega Sigma = Sigma)
so the duality gap never needs Omega explicitly.

The objectives, B and the predictions take either container
(core/mtl_data.py): their per-task reductions are the container's own
methods (``task_xt``, ``sum_over_n``, ``predictions``, ``task_sums``). Over
packed rows those are segment reductions that materialize no padded or
per-row copy of X or W.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .losses import Loss
from .mtl_data import MTLData
from .sigma_view import SigmaView

Array = jax.Array


def compute_B(data: MTLData, alpha: Array) -> Array:
    """B matrix, columns b_i = (1/n_i) X_i^T alpha_[i].  alpha: (m, n_max),
    or (R,) over packed rows."""
    masked = alpha * data.mask  # safety: padding contributes nothing
    b = data.task_xt(masked) / data.n[:, None].astype(data.x.dtype)
    return b.T  # (d, m)


def weights_from_alpha(data: MTLData, alpha: Array, sigma, lam: float) -> Array:
    """W(alpha) = (1/lambda) B Sigma, returned as (m, d) rows = tasks.

    ``sigma`` may be a dense (m, m) array or a SigmaView; the dense branch
    keeps the historical expression bit-identical."""
    B = compute_B(data, alpha)  # (d, m)
    if isinstance(sigma, SigmaView):
        return sigma.matvec(B.T) / lam  # Sigma symmetric: (B Sigma)^T = Sigma B^T
    return (B @ sigma).T / lam  # (m, d)


def quad_term(data: MTLData, alpha: Array, sigma) -> Array:
    """alpha^T K alpha = tr(Sigma B^T B).

    For a SigmaView, tr(Sigma B^T B) = sum_{i,d} (B^T)_{id} (Sigma B^T)_{id}
    — two factor matvecs, never a dense Sigma."""
    B = compute_B(data, alpha)
    if isinstance(sigma, SigmaView):
        Bt = B.T  # (m, d)
        return jnp.sum(Bt * sigma.matvec(Bt))
    return jnp.einsum("ij,ji->", sigma, B.T @ B)


def dual_objective(
    data: MTLData, alpha: Array, sigma: Array, lam: float, loss: Loss
) -> Array:
    """D(alpha) of Eq. (2)."""
    quad = quad_term(data, alpha, sigma)
    conj = loss.conjugate(-alpha, data.y) * data.mask
    conj_term = data.sum_over_n(conj)
    return -quad / (2.0 * lam) - conj_term


def primal_objective(
    data: MTLData, W: Array, omega: Array, lam: float, loss: Loss
) -> Array:
    """P(W) of Eq. (1) with explicit Omega (precision matrix). W: (m, d)."""
    z = data.predictions(W)
    emp = data.sum_over_n(loss.value(z, data.y) * data.mask)
    reg = 0.5 * lam * jnp.einsum("id,ij,jd->", W, omega, W)
    return emp + reg


def primal_objective_from_alpha(
    data: MTLData, alpha: Array, sigma: Array, lam: float, loss: Loss
) -> Array:
    """P(W(alpha)) using tr(W Omega W^T) = tr(Sigma B^T B)/lambda^2."""
    W = weights_from_alpha(data, alpha, sigma, lam)
    z = data.predictions(W)
    emp = data.sum_over_n(loss.value(z, data.y) * data.mask)
    reg = quad_term(data, alpha, sigma) / (2.0 * lam)
    return emp + reg


def duality_gap(
    data: MTLData, alpha: Array, sigma: Array, lam: float, loss: Loss
) -> Array:
    """G(alpha) = P(W(alpha)) - D(alpha) >= 0 (weak duality)."""
    return primal_objective_from_alpha(data, alpha, sigma, lam, loss) - dual_objective(
        data, alpha, sigma, lam, loss
    )


def local_subproblem_objective(
    data: MTLData,
    i: int,
    dalpha_i: Array,
    alpha: Array,
    w_i: Array,
    sigma_ii: Array,
    rho: float,
    lam: float,
    loss: Loss,
    m: int,
) -> Array:
    """D_i^rho of Eq. (4) for one task (used in tests / Theta measurement).

    D_i^rho = -(1/n_i) sum_j l*(-(alpha_j + dalpha_j))
              -(1/n_i) sum_j dalpha_j w_i^T x_j
              -(1/(2 lam m)) alpha^T K alpha
              -(rho/(2 lam)) dalpha^T K_[ii] dalpha
    with K_[ii] = (sigma_ii/n_i^2) X_i X_i^T.
    """
    xi, yi, mi = data.x[i], data.y[i], data.mask[i]
    ni = data.n[i].astype(xi.dtype)
    quad_global = quad_term(data, alpha, _sigma_placeholder(sigma_ii, alpha, data))
    # NOTE: callers that need the exact constant term pass the full sigma via
    # local_subproblem_objective_full; the constant does not affect argmax.
    del quad_global
    conj = loss.conjugate(-(alpha[i] + dalpha_i), yi) * mi
    t1 = -jnp.sum(conj) / ni
    t2 = -jnp.sum(dalpha_i * (xi @ w_i) * mi) / ni
    r = xi.T @ (dalpha_i * mi)
    t3 = -(rho * sigma_ii / (2.0 * lam * ni**2)) * jnp.sum(r * r)
    return t1 + t2 + t3


def _sigma_placeholder(sigma_ii, alpha, data):
    return jnp.eye(data.m, dtype=alpha.dtype)


def local_subproblem_objective_full(
    data: MTLData,
    i: int,
    dalpha_i: Array,
    alpha: Array,
    w_i: Array,
    sigma: Array,
    rho: float,
    lam: float,
    loss: Loss,
) -> Array:
    """D_i^rho including the constant -(1/(2 lam m)) alpha^T K alpha term."""
    base = local_subproblem_objective(
        data, i, dalpha_i, alpha, w_i, sigma[i, i], rho, lam, loss, data.m
    )
    const = -quad_term(data, alpha, sigma) / (2.0 * lam * data.m)
    return base + const


def predictions(data: MTLData, W: Array) -> Array:
    """z_j^i = w_i^T x_j^i, (m, n_max); (R,) over packed rows."""
    return data.predictions(W)


def task_scores(W: Array, X: Array, tasks: Array) -> Array:
    """Per-row scores z_n = w_{tasks[n]}^T x_n for flat request batches.

    The single scoring kernel shared by the estimator's predict path and
    the batched serving engine (serve/mtl.py) — W: (m, d), X: (n, d),
    tasks: (n,) int -> (n,)."""
    return jnp.einsum("nd,nd->n", X, W[tasks])


def error_rate(data: MTLData, W: Array) -> Array:
    """Masked averaged-over-tasks classification error (paper's metric)."""
    z = predictions(data, W)
    wrong = (jnp.sign(z) != jnp.sign(data.y)).astype(jnp.float32) * data.mask
    per_task = data.task_sums(wrong) / jnp.maximum(data.task_sums(data.mask), 1.0)
    return jnp.mean(per_task)


def rmse(data: MTLData, W: Array) -> Array:
    """Masked global RMSE over all test points (School metric)."""
    z = predictions(data, W)
    se = (z - data.y) ** 2 * data.mask
    return jnp.sqrt(jnp.sum(se) / jnp.maximum(jnp.sum(data.mask), 1.0))


def explained_variance(data: MTLData, W: Array) -> Array:
    """Explained variance as in Argyriou et al. (School): 1 - SSE/Var(y)."""
    z = predictions(data, W)
    msk = data.mask
    tot = jnp.maximum(jnp.sum(msk), 1.0)
    ybar = jnp.sum(data.y * msk) / tot
    sse = jnp.sum((z - data.y) ** 2 * msk)
    svar = jnp.sum((data.y - ybar) ** 2 * msk)
    return 1.0 - sse / jnp.maximum(svar, 1e-12)
