"""jit'd wrapper: layout adaptation (B,S,H,HD) <-> (B,H,S,HD) + padding."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import interpret_mode
from .flash_kernel import flash_attention

Array = jax.Array


def flash_attention_bshd(
    q: Array,  # (B, S, H, HD) — model layout
    k: Array,
    v: Array,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
) -> Array:
    B, S, H, HD = q.shape
    Sk = k.shape[1]
    bq = min(block_q, S)
    bk = min(block_k, Sk)
    pad_q = (-S) % bq
    pad_k = (-Sk) % bk
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        # padded keys sit at positions >= Sk; causal masking handles them for
        # decoder use; for non-causal padding would need an explicit mask.
        assert causal, "non-causal padding unsupported; pre-pad inputs"
    out = flash_attention(
        qt, kt, vt, causal, window, bq, bk, interpret=interpret_mode()
    )
    out = out[:, :, :S] if pad_q else out
    return jnp.moveaxis(out, 1, 2)
