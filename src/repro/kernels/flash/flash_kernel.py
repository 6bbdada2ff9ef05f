"""Pallas TPU flash-attention forward kernel (causal / sliding-window).

Grid: (batch, heads, num_q_blocks, num_kv_blocks) with the kv dimension
innermost (sequential on TPU); online-softmax running stats live in VMEM
scratch that persists across the kv loop:

    m (BQ,)       running row max
    l (BQ,)       running denominator
    acc (BQ, HD)  running numerator

BlockSpecs stage (BQ, HD) query tiles and (BK, HD) key/value tiles in VMEM;
the (BQ, BK) score tile exists only in VMEM/VREGs — the HBM score-tile
traffic of the jnp reference path (see docs/DESIGN.md §7) disappears.
Causal masking is positional; fully-masked kv blocks still execute in this
baseline kernel (the block-skip optimization is measured separately).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array
NEG_INF = -1e30


def _kernel(
    q_ref,  # (1, 1, BQ, HD)
    k_ref,  # (1, 1, BK, HD)
    v_ref,  # (1, 1, BK, HD)
    o_ref,  # (1, 1, BQ, HD)
    m_scr,  # (BQ,)
    l_scr,  # (BQ,)
    acc_scr,  # (BQ, HD)
    *,
    bq: int,
    bk: int,
    nk: int,
    causal: bool,
    window: int,
    scale: float,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (BQ, BK)

    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + p @ v_ref[0, 0].astype(jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _done():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention(
    q: Array,  # (B, H, S, HD)
    k: Array,  # (B, H, Sk, HD)
    v: Array,  # (B, H, Sk, HD)
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    *,
    interpret: bool,
) -> Array:
    B, H, S, HD = q.shape
    Sk = k.shape[2]
    bq = min(block_q, S)
    bk = min(block_k, Sk)
    assert S % bq == 0 and Sk % bk == 0, "pad seq to block multiples first"
    nq, nk = S // bq, Sk // bk
    scale = 1.0 / (HD**0.5)

    kern = functools.partial(
        _kernel, bq=bq, bk=bk, nk=nk, causal=causal, window=window, scale=scale
    )
    return pl.pallas_call(
        kern,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, HD), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, HD), lambda b, h, qi, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, bk, HD), lambda b, h, qi, ki: (b, h, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, HD), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, HD), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, HD), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
