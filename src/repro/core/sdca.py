"""Local SDCA (paper Algorithm 2) — naive and block-Gram forms.

Both act on ONE task's arrays and are vmapped over tasks by the driver: its
padded (n_max, d) block, or, with ``offset``, the packed rows of all tasks
(core/mtl_data.py:PackedMTLData), of which the task's sample j is row
``offset + j``; ``dalpha`` is then indexed by j and has length ``n_cap``.
Given the task's current dual variables ``alpha_i`` (all tasks' packed
ones, with ``offset``) and weight vector ``w_i``, they produce the approximate subproblem solution ``dalpha`` and the
un-normalized update direction ``r = X_i^T dalpha`` (so that
``delta_b_i = eta * r / n_i``).

naive      : literal Algorithm 2 — one coordinate per step, each step does a
             d-dim inner product + axpy. Reference semantics.
block_gram : TPU adaptation (see docs/DESIGN.md §4). H steps are processed in
             blocks of B sampled coordinates: the d-dim work becomes three
             matmuls per block (q = X_blk w, G = X_blk X_blk^T,
             r += X_blk^T delta) and the sequential part runs on the B x B
             Gram block only. Produces the *exact same iterate sequence* as
             naive for the same sampled coordinate order (duplicates within a
             block included), because inner products are corrected
             incrementally through G and a repeated coordinate's dual
             through a (B, B) same-coordinate mask; dalpha is gathered
             once and scattered once per block.

Engines do not call these functions directly: they resolve a named backend
through ``repro.core.solver_backends`` (docs/DESIGN.md §5), which wraps the
math here (and the Pallas kernels in repro.kernels.sdca) behind one
``solve(...)`` contract.

Sharding: when ``axis_name`` is given (feature dim d sharded over a mesh
axis), the d-contractions are psum'ed. naive then needs 3 collectives per
coordinate; block_gram needs 3 per block — this is the communication
argument for the block form (docs/DESIGN.md §7).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .losses import Loss

Array = jax.Array


def sample_coords(key: Array, H: int, n_i: Array, n_max: int) -> Array:
    """H coordinate indices uniform in [0, n_i) (paper: with replacement)."""
    u = jax.random.uniform(key, (H,))
    return jnp.minimum((u * n_i.astype(u.dtype)).astype(jnp.int32), n_i - 1)


def _dalpha0(alpha_i, y, offset, n_cap):
    """Zero dalpha: alpha_i's shape, or (n_cap,) over packed rows. The
    ``+ y[0] * 0`` gives it the inputs' varying-manual-axes type under
    shard_map."""
    if offset is None:
        return jnp.zeros_like(alpha_i) + y[0] * 0
    return jnp.zeros((n_cap,), alpha_i.dtype) + y[0] * 0


def _psum(x, axis_name):
    return jax.lax.psum(x, axis_name) if axis_name is not None else x


def local_sdca_naive(
    x: Array,  # (n_max, d)    [d possibly a shard]
    y: Array,  # (n_max,)
    alpha_i: Array,  # (n_max,)
    w_i: Array,  # (d,)
    n_i: Array,  # scalar int
    sigma_ii: Array,  # scalar
    coords: Array,  # (H,) int32
    rho: float,
    lam: float,
    loss: Loss,
    axis_name: Optional[str] = None,
    offset: Optional[Array] = None,
    n_cap: Optional[int] = None,
) -> Tuple[Array, Array]:
    """Algorithm 2, one coordinate at a time. Returns (dalpha, r)."""
    nf = jnp.maximum(n_i.astype(x.dtype), 1.0)
    kappa = rho * sigma_ii / (lam * nf)

    def body(h, carry):
        dalpha, r = carry
        j = coords[h]
        row = j if offset is None else offset + j
        xj = x[row]
        # d-contractions (collective per coordinate when d is sharded)
        wx = _psum(jnp.dot(xj, w_i), axis_name)
        xr = _psum(jnp.dot(xj, r), axis_name)
        xx = _psum(jnp.dot(xj, xj), axis_name)
        c = wx + kappa * xr
        a = kappa * xx
        atilde = alpha_i[row] + dalpha[j]
        delta = loss.sdca_delta(atilde, c, a, y[row])
        dalpha = dalpha.at[j].add(delta)
        r = r + delta * xj
        return dalpha, r

    H = coords.shape[0]
    dalpha0 = _dalpha0(alpha_i, y, offset, n_cap)
    # + x[0]*0 keeps the carry's varying-manual-axes equal to the loop
    # output's under shard_map (x may vary over a 'pod' sample axis)
    r0 = jnp.zeros_like(w_i) + x[0] * 0
    return jax.lax.fori_loop(0, H, body, (dalpha0, r0))


def local_sdca_block(
    x: Array,
    y: Array,
    alpha_i: Array,
    w_i: Array,
    n_i: Array,
    sigma_ii: Array,
    coords: Array,  # (H,) int32; H must be a multiple of block
    rho: float,
    lam: float,
    loss: Loss,
    block: int = 64,
    axis_name: Optional[str] = None,
    offset: Optional[Array] = None,
    n_cap: Optional[int] = None,
) -> Tuple[Array, Array]:
    """Block-Gram Local SDCA. Same iterates as naive, MXU-shaped."""
    H = coords.shape[0]
    assert H % block == 0, f"H={H} must be a multiple of block={block}"
    nb = H // block
    coords_b = coords.reshape(nb, block)
    nf = jnp.maximum(n_i.astype(x.dtype), 1.0)
    kappa = rho * sigma_ii / (lam * nf)

    def blk_fn(carry, cb):
        dalpha, r = carry
        rb = cb if offset is None else offset + cb  # the block's rows
        xb = x[rb]  # (B, d)
        q = _psum(xb @ w_i, axis_name)  # (B,)
        xr = _psum(xb @ r, axis_name)  # (B,)
        G = _psum(xb @ xb.T, axis_name)  # (B, B)
        # the block's starting duals, gathered once; the recursion folds
        # in earlier draws of the same coordinate, and dalpha takes the
        # block's deltas in one scatter
        at0 = alpha_i[rb] + dalpha[cb]
        deltas = sdca_block_solve(G, q, xr, at0, y[rb], cb, kappa, loss)
        dalpha = add_block(dalpha, cb, deltas)
        r = r + xb.T @ deltas
        return (dalpha, r), None

    dalpha0 = _dalpha0(alpha_i, y, offset, n_cap)
    r0 = jnp.zeros_like(w_i) + x[0] * 0  # see local_sdca_naive note
    (dalpha, r), _ = jax.lax.scan(blk_fn, (dalpha0, r0), coords_b)
    return dalpha, r


def add_block(dalpha: Array, cb: Array, deltas: Array) -> Array:
    """``dalpha`` with one block's deltas added at its coords ``cb``.

    Every draw of a coordinate writes the same value, its dalpha plus all
    of the block's deltas for it, so the result does not depend on the
    order in which the scatter meets repeated indices, which XLA leaves
    open: two programs running the same round agree bit for bit."""
    same = cb[:, None] == cb[None, :]
    total = jnp.sum(jnp.where(same, deltas[None, :], 0.0), axis=1)
    return dalpha.at[cb].set(dalpha[cb] + total)


def sdca_block_solve(
    G: Array,  # (B, B) Gram of this block's rows (psum'ed)
    q: Array,  # (B,)   X_blk @ w (psum'ed)
    xr: Array,  # (B,)   X_blk @ r_prev (psum'ed)
    at0: Array,  # (B,)   alpha + dalpha at the block's coords, block start
    yb: Array,  # (B,)   labels of the block's rows
    cb: Array,  # (B,)   coords of this block
    kappa: Array,
    loss: Loss,
) -> Array:
    """Collective-free B-step scalar recursion of ONE block. Returns the
    block's deltas; the caller adds them into dalpha with ``add_block``.

    Step k's dual is ``at0[k]`` plus the deltas of earlier steps that drew
    the same coordinate (``deltas[k:]`` are still 0), so only (B,) arrays
    ride in the loop, as in the Pallas kernel (kernels/sdca). Step k
    writes its delta with a select over the block's lanes, not
    ``deltas.at[k].set``: vmapped over tasks that one-element write lowers
    to a scatter, whose in-bounds predicate XLA computes at every step."""
    B = cb.shape[0]
    same = (cb[:, None] == cb[None, :]).astype(q.dtype)  # (B, B)
    # derived from cb so that, vmapped, the lanes carry the task axis too:
    # the compare then fuses into the select, not a kernel of its own
    lane = jnp.arange(B) + cb * 0

    def body(k, deltas):
        # c_k = q_k + kappa * (x_k^T r + sum_{k'<k} G[k,k'] delta_k')
        corr = jnp.dot(G[k], deltas)  # deltas[k:] are still 0
        c = q[k] + kappa * (xr[k] + corr)
        a = kappa * G[k, k]
        atilde = at0[k] + jnp.sum(same[k] * deltas)
        delta = loss.sdca_delta(atilde, c, a, yb[k])
        return jnp.where(lane == k, delta, deltas)

    # derive from q so the carry carries the same varying-manual-axes
    # type as the inputs under shard_map
    return jax.lax.fori_loop(0, B, body, q * 0.0)
