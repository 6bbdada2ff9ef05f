"""jit'd wrapper: full SSD forward using the Pallas chunk kernel for the
intra-chunk work + XLA associative scan for the inter-chunk recurrence.
Drop-in equivalent of models/ssm.ssd_chunked (tested against it and the
naive recurrence)."""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .. import interpret_mode
from .ssd_kernel import ssd_chunk_kernel

Array = jax.Array


def ssd_forward(
    x: Array,  # (B, L, H, P) fp32
    dt: Array,  # (B, L, H)
    A: Array,  # (H,)
    Bm: Array,  # (B, L, H, N)
    Cm: Array,
    chunk: int = 64,
) -> Tuple[Array, Array]:
    B_, L, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, L)
    pad = (-L) % Q
    if pad:
        pad4 = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        x, dt, Bm, Cm = pad4(x), pad4(dt), pad4(Bm), pad4(Cm)
    nc = (L + pad) // Q

    def to_chunks(a):  # (B, L, ...) -> (B, H, nc, Q, ...)
        a = a.reshape((B_, nc, Q) + a.shape[2:])
        return jnp.moveaxis(a, 3, 1)  # (B, H, nc, Q, ...)

    xc = to_chunks(x)
    dtc = to_chunks(dt[..., None])[..., 0]
    Bc = to_chunks(Bm)
    Cc = to_chunks(Cm)

    Y_intra, S_local, a_tot = ssd_chunk_kernel(
        xc, dtc, A, Bc, Cc, interpret=interpret_mode()
    )

    # inter-chunk: associative scan over (a_tot, S_local) along chunk axis
    def combine(left, right):
        a1, s1 = left
        a2, s2 = right
        return a1 * a2, a2[..., None, None] * s1 + s2

    a_inc, S_inc = jax.lax.associative_scan(combine, (a_tot, S_local), axis=2)
    S_prev = jnp.concatenate(
        [jnp.zeros_like(S_inc[:, :, :1]), S_inc[:, :, :-1]], axis=2
    )  # (B, H, nc, N, P)

    la = dtc * A[None, :, None, None]
    cum = jnp.cumsum(la, axis=-1)
    Y_inter = jnp.einsum(
        "bhcqn,bhcnp->bhcqp", Cc * jnp.exp(cum)[..., None], S_prev
    )
    Y = Y_intra + Y_inter  # (B, H, nc, Q, P)
    Y = jnp.moveaxis(Y, 1, 3).reshape(B_, nc * Q, H, P)[:, :L]
    final_state = jnp.swapaxes(S_inc[:, :, -1], -1, -2)  # (B, H, P, N)
    return Y, final_state
