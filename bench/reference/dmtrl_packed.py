"""Plain reference of DMTRL's Algorithm 1 (Liu, Pan & Ho, KDD 2017) over
packed rows.

The algorithm, the coordinate draws and the key schedule are those of
``bench/reference/dmtrl.py`` (see there), on the rows of all tasks stored
back to back: task i's sample j is row off_i + j, off the exclusive
cumulative sum of the task sizes n. Nothing of the program under test is
imported.

Each coordinate step of a task reads its one row x[off_i + j]. The
objectives need b_i = X_i^T alpha_i / n_i and the predictions
x_r . w_task(r) of every row: both are computed block by block of rows,
each row with its task, so that the reference holds no padded or second
copy of the rows. The W-step and the objectives run in ``dtype`` at the
highest matmul precision; the Omega-step and rho run in float64 on the
host.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dmtrl import (
    HIGHEST,
    _conj_at_minus,
    _delta,
    _loss,
    omega_step,
    rho_lemma10,
)

BLOCK = 4096  # rows a block of the objectives reads


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _round(loss, lam, eta, H, n_cap, x, y, n, off, task, alpha, W, sigma, key, rho):
    m = n.shape[0]
    keys = jax.vmap(lambda t: jax.random.fold_in(jax.random.fold_in(key, t), 0))(
        jnp.arange(m, dtype=jnp.int32)
    )
    dt = x.dtype

    def one_task(ni, oi, wi, sii, ki):
        u = jax.random.uniform(ki, (H,))
        coords = jnp.minimum((u * ni.astype(u.dtype)).astype(jnp.int32), ni - 1)
        kappa = (rho * sii / (lam * jnp.maximum(ni, 1).astype(dt))).astype(dt)

        def body(h, carry):
            da, r = carry
            j = coords[h]
            xj = x[oi + j]
            c = jnp.dot(xj, wi, precision=HIGHEST) + kappa * jnp.dot(
                xj, r, precision=HIGHEST
            )
            a = kappa * jnp.dot(xj, xj, precision=HIGHEST)
            d = _delta(loss, alpha[oi + j] + da[j], c, a, y[oi + j]).astype(dt)
            return da.at[j].add(d), r + d * xj

        return jax.lax.fori_loop(0, H, body, (jnp.zeros((n_cap,), dt), jnp.zeros_like(wi)))

    da, r = jax.vmap(one_task)(n, off, W, jnp.diagonal(sigma), keys)
    j = jnp.arange(x.shape[0], dtype=jnp.int32) - off[task]
    alpha = alpha + eta * da[task, j]
    db = (eta * r / jnp.maximum(n, 1)[:, None].astype(dt)).astype(dt)
    W = W + (jnp.matmul(sigma, db, precision=HIGHEST) / lam).astype(dt)
    return alpha, W


def _blocks(R: int):
    """(number of blocks, rows a block): the last block ends at row R and
    counts only the rows the one before it did not."""
    rows = min(BLOCK, R)
    return -(-R // rows), rows


def _block(b, rows, R, *arrays):
    s = jnp.minimum(b * rows, R - rows)
    fresh = s + jnp.arange(rows) >= b * rows
    return fresh, [jax.lax.dynamic_slice_in_dim(a, s, rows) for a in arrays]


@functools.partial(jax.jit, static_argnums=(0, 1))
def _objectives(loss, lam, x, y, n, task, alpha, sigma):
    """(primal, dual) at W(alpha), and W(alpha)."""
    (R, d), m, dt = x.shape, n.shape[0], x.dtype
    nblocks, rows = _blocks(R)
    nf = jnp.maximum(n, 1).astype(dt)
    tasks = jnp.arange(m)[:, None]

    def b_block(b, acc):
        fresh, (xb, ab, tb) = _block(b, rows, R, x, alpha, task)
        onehot = (tb[None, :] == tasks) & fresh[None, :]
        return acc + jnp.matmul(jnp.where(onehot, ab[None, :], 0), xb, precision=HIGHEST)

    B = jax.lax.fori_loop(0, nblocks, b_block, jnp.zeros((m, d), dt)) / nf[:, None]
    quad = jnp.sum(sigma * jnp.matmul(B, B.T, precision=HIGHEST))
    W = jnp.matmul(sigma, B, precision=HIGHEST) / lam

    def emp_block(b, acc):
        fresh, (xb, yb, tb) = _block(b, rows, R, x, y, task)
        z = jnp.take_along_axis(jnp.matmul(xb, W.T, precision=HIGHEST), tb[:, None], axis=1)[:, 0]
        return acc + jnp.sum(jnp.where(fresh, _loss(loss, z, yb) / nf[tb], 0))

    emp = jax.lax.fori_loop(0, nblocks, emp_block, jnp.zeros((), dt))
    conj = jnp.sum(_conj_at_minus(loss, alpha, y) / nf[task])
    return emp + quad / (2.0 * lam), -quad / (2.0 * lam) - conj, W


def fit(x, y, n, *, loss, lam, eta, outer_iters, rounds, H, seed,
        jitter=1e-6, dtype=jnp.float32):
    """Algorithm 1 on packed rows x (R, d), y (R,) of tasks of sizes n
    (R = sum n). Returns what ``bench/reference/dmtrl.py:fit`` returns, with
    alpha (R,) in the rows' order."""
    n_host = np.asarray(n, np.int64)
    off_host = np.cumsum(n_host) - n_host
    x, y = jnp.asarray(x, dtype), jnp.asarray(y, dtype)
    n = jnp.asarray(n_host, jnp.int32)
    off = jnp.asarray(off_host, jnp.int32)
    task = jnp.asarray(np.repeat(np.arange(n_host.shape[0], dtype=np.int32), n_host))
    n_cap = int(n_host.max())
    m, d = n_host.shape[0], x.shape[1]
    alpha = jnp.zeros((x.shape[0],), dtype)
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
                loss, lam, eta, H, n_cap, x, y, n, off, task, alpha, W, sig,
                round_keys[t], jnp.asarray(rho, dtype),
            )
            p, dd, _ = _objectives(loss, lam, x, y, n, task, alpha, sig)
            primal.append(float(p))
            dual.append(float(dd))
        sigma = omega_step(np.asarray(W, np.float64), jitter)
        _, _, W = _objectives(loss, lam, x, y, n, task, alpha, jnp.asarray(sigma, dtype))
    return {
        "W": np.asarray(W, np.float64),
        "alpha": np.asarray(alpha, np.float64),
        "sigma": sigma,
        "primal": np.asarray(primal),
        "dual": np.asarray(dual),
    }
