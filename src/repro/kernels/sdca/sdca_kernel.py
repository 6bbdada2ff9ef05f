"""Pallas TPU kernels for the block-Gram SDCA inner update (docs/DESIGN.md §4).

Two entry points:

``sdca_block_kernel`` — ONE H-block per ``pallas_call`` (the ``pallas_block``
backend). ``w``/``r`` are re-streamed from HBM on every call, so a local
round of H iterations costs H/B kernel launches.

``sdca_round_kernel`` — ALL H-blocks of one local round fused into a single
``pallas_call`` (the ``pallas_round`` backend, docs/DESIGN.md §6): the task's
data block, ``w`` and the running correction ``r`` stay VMEM-resident across
blocks, and only ``(dalpha, r)`` leave the kernel.

Per-block pipeline (B = block size), shared by both kernels:
  phase A (MXU):  q  = w X_blk^T          (1, B)
                  xr = r X_blk^T          (1, B)
                  G  = X_blk X_blk^T      (B, B)
  phase B (VPU):  sequential fori_loop over the B coordinates on the
        VMEM-resident Gram block:
            c_k = q_k + kappa * (xr_k + G[k, :] . deltas)
            a_k = kappa * G[k, k]
            delta_k = closed-form argmax (hinge / squared / smoothed hinge)

Layout rules the TPU compiler (Mosaic) imposes, and how they are met:
  * every vector operand is 2-D and lane-aligned: ``d`` is zero-padded to a
    multiple of 128 (zero features change no inner product), ``n_max`` to a
    multiple of 8 sublanes;
  * rows are read from refs with ``ref[pl.ds(k, 1), :]``, never with
    ``lax.dynamic_slice`` on a loaded value;
  * a lane of a (1, B) vector is read as a masked sum against a one-hot
    lane mask (exact: every other term is zero);
  * scalars, labels, dual variables and int32 coordinate ids live in SMEM,
    and the round kernel scatter-adds ``dalpha`` through its SMEM ref.

Both kernels compile for the TPU and run in the Pallas interpreter on the
CPU (``interpret``), which the ops layer decides from the platform.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

_GAMMA = 0.5  # smoothed-hinge knee (must match core.losses)
_EPS = 1e-12
_LANE = 128
_SUBLANE = 8
_HIGHEST = jax.lax.Precision.HIGHEST

# VMEM budget of the round kernel's resident task block, in bytes of the
# padded (n_max, d) float32 block. The vmapped kernel double-buffers it
# across tasks, so the compiler limit is set to twice this plus scratch.
ROUND_VMEM_BUDGET = 16 * 2**20


def _delta_hinge(atilde, c, a, y):
    a = jnp.maximum(a, _EPS)
    anew = y * jnp.clip(y * (atilde + (y - c) / a), 0.0, 1.0)
    return anew - atilde


def _delta_squared(atilde, c, a, y):
    return (y - c - atilde) / (1.0 + a)


def _delta_smoothed_hinge(atilde, c, a, y):
    anew_u = atilde + (y - c - _GAMMA * atilde) / (_GAMMA + a)
    anew = y * jnp.clip(y * anew_u, 0.0, 1.0)
    return anew - atilde


_DELTAS = {
    "hinge": _delta_hinge,
    "squared": _delta_squared,
    "smoothed_hinge": _delta_smoothed_hinge,
}
SUPPORTED_LOSSES = tuple(_DELTAS)


def _round_up(x: int, k: int) -> int:
    return -(-x // k) * k


def _check_loss(loss: str) -> None:
    if loss not in _DELTAS:
        raise ValueError(
            f"the SDCA kernels have a closed-form delta for "
            f"{SUPPORTED_LOSSES}, not {loss!r}"
        )


def _dot_nt(a, b):
    """a @ b.T in float32 on the MXU."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=jnp.float32,
    )


def _pick(v, onehot):
    """Lane k of a (1, B) vector as a (1, 1) vector (exact masked sum)."""
    return jnp.sum(jnp.where(onehot, v, 0.0), axis=1, keepdims=True)


def _block_step(k, deltas, g_ref, q, xr, kappa):
    """Shared part of step k of the Gram recursion: (onehot, c_k, a_k)."""
    onehot = jax.lax.broadcasted_iota(jnp.int32, deltas.shape, 1) == k
    grow = g_ref[pl.ds(k, 1), :]  # (1, B) row k of the Gram block
    corr = jnp.sum(grow * deltas, axis=1, keepdims=True)  # deltas[k:] are 0
    c = _pick(q, onehot) + kappa * (_pick(xr, onehot) + corr)
    a = kappa * _pick(grow, onehot)
    return onehot, c, a


def _block_kernel(
    xb_ref,  # (B, DT) tile of the sampled rows
    w_ref,  # (1, DT)
    r_ref,  # (1, DT)
    at0_ref,  # (1, B) initial alpha~ per slot
    y_ref,  # (1, B)
    same_ref,  # (B, B) 1.0 where two slots sample the same coordinate
    kappa_ref,  # (1, 1) SMEM
    out_ref,  # (1, B) deltas
    q_acc,  # scratch (1, B)
    xr_acc,  # scratch (1, B)
    g_acc,  # scratch (B, B)
    *,
    loss: str,
    n_tiles: int,
):
    ti = pl.program_id(0)

    @pl.when(ti == 0)
    def _init():
        q_acc[...] = jnp.zeros_like(q_acc)
        xr_acc[...] = jnp.zeros_like(xr_acc)
        g_acc[...] = jnp.zeros_like(g_acc)

    xb = xb_ref[...]
    # phase A: accumulate the three d-contractions over the d tiles
    q_acc[...] += _dot_nt(w_ref[...], xb)
    xr_acc[...] += _dot_nt(r_ref[...], xb)
    g_acc[...] += _dot_nt(xb, xb)

    @pl.when(ti == n_tiles - 1)
    def _solve():
        kappa = kappa_ref[0, 0]
        q, xr = q_acc[...], xr_acc[...]
        at0, y = at0_ref[...], y_ref[...]
        delta_fn = _DELTAS[loss]

        def body(k, deltas):
            onehot, c, a = _block_step(k, deltas, g_acc, q, xr, kappa)
            # duplicate coordinates: alpha~ includes earlier deltas on them
            dup = jnp.sum(same_ref[pl.ds(k, 1), :] * deltas, axis=1, keepdims=True)
            atilde = _pick(at0, onehot) + dup
            d = delta_fn(atilde, c, a, _pick(y, onehot))
            return jnp.where(onehot, d, deltas)

        out_ref[...] = jax.lax.fori_loop(0, q.shape[1], body, jnp.zeros_like(q))


def sdca_block_kernel(
    xb: Array,  # (B, d)
    w: Array,  # (d,)
    r: Array,  # (d,)
    at0: Array,  # (B,)
    y: Array,  # (B,)
    cb: Array,  # (B,) int32 coordinate ids (duplicate detection)
    kappa: Array,  # scalar
    loss: str,
    *,
    interpret: bool,
    d_tile: int = 512,
) -> Array:
    """Deltas (B,) for one block of sampled coordinates, d tiled over a grid."""
    _check_loss(loss)
    B, d = xb.shape
    d_tile = min(_round_up(d_tile, _LANE), _round_up(d, _LANE))
    d_pad = _round_up(d, d_tile)
    n_tiles = d_pad // d_tile
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    row = lambda v: jnp.pad(f32(v), (0, d_pad - d)).reshape(1, d_pad)
    xb = jnp.pad(f32(xb), ((0, 0), (0, d_pad - d)))
    cb = jnp.asarray(cb, jnp.int32)
    same = (cb[:, None] == cb[None, :]).astype(jnp.float32)

    kern = functools.partial(_block_kernel, loss=loss, n_tiles=n_tiles)
    out = pl.pallas_call(
        kern,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((B, d_tile), lambda i: (0, i)),
            pl.BlockSpec((1, d_tile), lambda i: (0, i)),
            pl.BlockSpec((1, d_tile), lambda i: (0, i)),
            pl.BlockSpec((1, B), lambda i: (0, 0)),
            pl.BlockSpec((1, B), lambda i: (0, 0)),
            pl.BlockSpec((B, B), lambda i: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, B), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, B), jnp.float32),
        name="sdca_block",
        scratch_shapes=[
            pltpu.VMEM((1, B), jnp.float32),
            pltpu.VMEM((1, B), jnp.float32),
            pltpu.VMEM((B, B), jnp.float32),
        ],
        interpret=interpret,
    )(
        xb, row(w), row(r), f32(at0).reshape(1, B), f32(y).reshape(1, B),
        same, f32(kappa).reshape(1, 1),
    )
    return out[0]


def _round_kernel(
    x_ref,  # (n_pad, d_pad) VMEM: the task's whole (padded) data block
    w_ref,  # (1, d_pad) VMEM
    ids_ref,  # (1, H) int32 SMEM: sampled coordinates
    y_ref,  # (1, n_pad) SMEM
    alpha_ref,  # (1, n_pad) SMEM: current dual block
    kappa_ref,  # (1, 1) SMEM
    dalpha_ref,  # out (1, n_pad) SMEM: scatter-accumulated dual update
    r_ref,  # out (1, d_pad) VMEM: X^T dalpha
    xb_ref,  # scratch (B, d_pad) VMEM: this block's gathered rows
    g_ref,  # scratch (B, B) VMEM: this block's Gram
    *,
    loss: str,
    n_blocks: int,
    block: int,
):
    kappa = kappa_ref[0, 0]
    delta_fn = _DELTAS[loss]

    def zero(i, carry):
        dalpha_ref[0, i] = 0.0
        return carry

    jax.lax.fori_loop(0, dalpha_ref.shape[1], zero, 0)
    r_ref[...] = jnp.zeros_like(r_ref)
    w = w_ref[...]

    def blk(b, carry):
        base = b * block

        def gather(k, c):
            j = ids_ref[0, base + k]
            xb_ref[pl.ds(k, 1), :] = x_ref[pl.ds(j, 1), :]
            return c

        jax.lax.fori_loop(0, block, gather, 0)
        xb = xb_ref[...]
        r = r_ref[...]
        q = _dot_nt(w, xb)
        xr = _dot_nt(r, xb)
        g_ref[...] = _dot_nt(xb, xb)

        def inner(k, deltas):
            onehot, c, a = _block_step(k, deltas, g_ref, q, xr, kappa)
            j = ids_ref[0, base + k]
            # alpha~ reads the scatter-accumulated dalpha, so a coordinate
            # drawn twice in one block sees its earlier update
            atilde = alpha_ref[0, j] + dalpha_ref[0, j]
            d = delta_fn(atilde, c, a, y_ref[0, j])  # (1, 1)
            dalpha_ref[0, j] = dalpha_ref[0, j] + jnp.sum(d)
            return jnp.where(onehot, d, deltas)

        deltas = jax.lax.fori_loop(
            0, block, inner, jnp.zeros((1, block), jnp.float32)
        )
        r_ref[...] = r + jnp.dot(
            deltas, xb, precision=_HIGHEST, preferred_element_type=jnp.float32
        )
        return carry

    jax.lax.fori_loop(0, n_blocks, blk, 0)


def sdca_round_kernel(
    x,  # (n_max, d)
    y,  # (n_max,)
    alpha_i,  # (n_max,)
    w,  # (d,)
    u,  # (H,) uniforms in [0, 1) derived from the per-round key
    n_i,  # scalar int: valid sample count
    kappa,  # scalar: rho * sigma_ii / (lambda * n_i)
    loss: str,
    *,
    interpret: bool,
    block: int = 64,
):
    """One fused local SDCA round: H = len(u) iterations in H/block Gram
    blocks, ONE pallas_call. Returns (dalpha, r), both float32.

    The coordinates are ``min(floor(u * n_i), n_i - 1)``, the exact
    ``sample_coords`` arithmetic, computed on the device before the call.
    The whole padded (n_max, d) task block stays in VMEM, so a block larger
    than ``ROUND_VMEM_BUDGET`` is refused with a ValueError; there is no
    fallback to another kernel (streaming rows from HBM is ROADMAP B4).
    """
    _check_loss(loss)
    H = u.shape[0]
    if H % block:
        raise ValueError(f"H={H} must be a multiple of block={block}")
    n_max, d = x.shape
    n_pad, d_pad = _round_up(n_max, _SUBLANE), _round_up(d, _LANE)
    nbytes = n_pad * d_pad * 4
    if nbytes > ROUND_VMEM_BUDGET:
        raise ValueError(
            f"pallas_round keeps the whole task block in VMEM: n_max={n_max} "
            f"x d={d} pads to {nbytes} bytes of float32, over its "
            f"{ROUND_VMEM_BUDGET}-byte budget"
        )
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    n = jnp.asarray(n_i, jnp.int32)
    ids = jnp.minimum((u * n.astype(u.dtype)).astype(jnp.int32), n - 1)
    # SMEM operands are (1, n) rows: under vmap the task axis is then a
    # squeezed leading block dim and the (1, n) block spans the whole array
    vec = lambda v: jnp.pad(f32(v), (0, n_pad - n_max)).reshape(1, n_pad)

    kern = functools.partial(
        _round_kernel, loss=loss, n_blocks=H // block, block=block
    )
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    scratch_bytes = (block * d_pad + block * block + 2 * d_pad) * 4
    dalpha, r = pl.pallas_call(
        kern,
        in_specs=[vmem, vmem, smem, smem, smem, smem],
        out_specs=(smem, vmem),
        out_shape=(
            jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
            jax.ShapeDtypeStruct((1, d_pad), jnp.float32),
        ),
        name="sdca_round",
        scratch_shapes=[
            pltpu.VMEM((block, d_pad), jnp.float32),
            pltpu.VMEM((block, block), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=2 * (nbytes + scratch_bytes) + 4 * 2**20
        ),
        interpret=interpret,
    )(
        jnp.pad(f32(x), ((0, n_pad - n_max), (0, d_pad - d))),
        jnp.pad(f32(w), (0, d_pad - d)).reshape(1, d_pad),
        ids.reshape(1, H),
        vec(y),
        vec(alpha_i),
        f32(kappa).reshape(1, 1),
    )
    return dalpha[0, :n_max], r[0, :d]
