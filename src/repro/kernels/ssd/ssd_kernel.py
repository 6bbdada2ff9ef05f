"""Pallas TPU kernel for the Mamba2 SSD chunk-local computation.

Per (batch, head, chunk) grid cell, entirely in VMEM (Q<=128, N,P<=128):
    cum      = cumsum(dt * A)                     (Q,)
    M        = exp(cum_t - cum_tau) . tril        (Q, Q)
    Y_intra  = ((C B^T) o M) @ (dt * x)           (Q, P)   two MXU matmuls
    S_local  = (B * exp(cum_Q - cum))^T @ (dt*x)  (N, P)   one MXU matmul
    a_tot    = exp(cum_Q)                         scalar
The inter-chunk recurrence (log-depth associative scan over a_tot/S_local)
stays in XLA — it is O(L/Q) tiny tensors and fuses well there.

Outputs: Y_intra (B,H,nc,Q,P), S_local (B,H,nc,N,P), a_tot (B,H,nc).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array


def _kernel(
    x_ref,  # (1, 1, 1, Q, P)
    dt_ref,  # (1, 1, 1, Q)
    a_ref,  # (1, 1)  A scalar for this head (SMEM-ish block)
    b_ref,  # (1, 1, 1, Q, N)
    c_ref,  # (1, 1, 1, Q, N)
    y_ref,  # (1, 1, 1, Q, P)
    s_ref,  # (1, 1, 1, N, P)
    atot_ref,  # (1, 1, 1)
    *,
    q_len: int,
):
    x = x_ref[0, 0, 0].astype(jnp.float32)  # (Q, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)  # (Q,)
    A = a_ref[0, 0]
    Bm = b_ref[0, 0, 0].astype(jnp.float32)  # (Q, N)
    Cm = c_ref[0, 0, 0].astype(jnp.float32)

    la = dt * A  # (Q,) log-decay per step (<= 0)
    cum = jnp.cumsum(la)  # inclusive
    u = x * dt[:, None]  # (Q, P)

    diff = cum[:, None] - cum[None, :]  # (Qt, Qtau)
    tri = (
        jax.lax.broadcasted_iota(jnp.int32, (q_len, q_len), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (q_len, q_len), 1)
    )
    M = jnp.where(tri, jnp.exp(diff), 0.0)
    CB = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Qt, Qtau)
    y_ref[0, 0, 0] = ((CB * M) @ u).astype(y_ref.dtype)

    decay_end = jnp.exp(cum[-1] - cum)  # (Q,)
    s_ref[0, 0, 0] = (
        jax.lax.dot_general(
            Bm * decay_end[:, None],
            u,
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    ).astype(s_ref.dtype)  # (N, P)
    atot_ref[0, 0, 0] = jnp.exp(cum[-1])


def ssd_chunk_kernel(
    x: Array,  # (B, H, nc, Q, P) fp32
    dt: Array,  # (B, H, nc, Q)
    A: Array,  # (H,)
    Bm: Array,  # (B, H, nc, Q, N)
    Cm: Array,  # (B, H, nc, Q, N)
    *,
    interpret: bool,
):
    B, H, nc, Q, P = x.shape
    N = Bm.shape[-1]
    a2d = jnp.tile(A[None, :], (B, 1)).astype(jnp.float32)  # (B, H) block input

    kern = functools.partial(_kernel, q_len=Q)
    return pl.pallas_call(
        kern,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1), lambda b, h, c: (b, h)),
            pl.BlockSpec((1, 1, 1, Q, N), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, N), lambda b, h, c: (b, h, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, N, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, h, c: (b, h, c)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nc, Q, P), jnp.float32),
            jax.ShapeDtypeStruct((B, H, nc, N, P), jnp.float32),
            jax.ShapeDtypeStruct((B, H, nc), jnp.float32),
        ],
        interpret=interpret,
    )(x, dt, a2d, Bm, Cm)
