"""MDS-shaped sentiment tasks as packed rows, made on the device from a seed.

Paper section 7.1's MDS setting (Blitzer et al.'s Multi-Domain Sentiment
reviews): 22 domain tasks over a d = 10,000 bag-of-words vocabulary, n_i
from 314 to 20,751 reviews. The rows follow ``repro.data.synthetic.mds_like``:
a shared +-1 sentiment lexicon over a quarter of the vocabulary, per-domain
weights the lexicon plus ``deviation`` N(0, 1) noise, ``active`` distinct
features a row with values U(0, 1) + 0.2, unit-norm rows, and labels +-1
with P(+1) = sigmoid(10 w_i.x).

The task sizes are drawn once from ``size_seed`` (the two published
extremes pinned, the others log-uniform between them), so that every seed
has one shape; the run's seed draws the rows. The rows are drawn and
written in chunks into one preallocated packed array, task after task
(``repro.core.mtl_data.PackedMTLData``), so set-up never holds a padded or
a second full copy of them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 8192  # rows drawn per call


def sizes(config: dict) -> np.ndarray:
    """Training rows per task: the published extremes and tasks - 2 sizes
    log-uniform between them, of which ``train_frac`` trains."""
    lo, hi = config["n_min"], config["n_max_task"]
    rng = np.random.RandomState(config["size_seed"])
    drawn = np.exp(rng.uniform(np.log(lo), np.log(hi), size=config["tasks"] - 2))
    full = np.concatenate([[lo, hi], drawn.astype(int)])
    return np.maximum(1, np.round(config["train_frac"] * full)).astype(np.int32)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _weights(key, tasks: int, d: int, lexicon: int, deviation: float):
    kl, ks, kd = jax.random.split(key, 3)
    words = jax.random.permutation(kl, d)[:lexicon]
    sign = jax.random.rademacher(ks, (lexicon,), jnp.float32)
    shared = jnp.zeros((d,), jnp.float32).at[words].set(sign)
    return shared[None, :] + deviation * jax.random.normal(kd, (tasks, d), jnp.float32)


@functools.partial(jax.jit, static_argnums=(6, 7), donate_argnums=(0, 1))
def _fill(x, y, W, task, key, start, rows: int, active: int):
    """Draw ``rows`` rows into x and y from row ``start`` on."""
    d = x.shape[1]
    ki, kv, kl = jax.random.split(key, 3)
    _, words = jax.lax.top_k(jax.random.uniform(ki, (rows, d)), active)
    vals = jax.random.uniform(kv, (rows, active)) + 0.2
    xc = jnp.zeros((rows, d), jnp.float32).at[jnp.arange(rows)[:, None], words].set(vals)
    xc = xc / jnp.linalg.norm(xc, axis=1, keepdims=True)
    t = jax.lax.dynamic_slice_in_dim(task, start, rows)
    z = jnp.take_along_axis(xc @ W.T, t[:, None], axis=1)[:, 0]
    yc = jnp.where(jax.random.uniform(kl, (rows,)) < jax.nn.sigmoid(10.0 * z), 1.0, -1.0)
    x = jax.lax.dynamic_update_slice_in_dim(x, xc, start, axis=0)
    y = jax.lax.dynamic_update_slice_in_dim(y, yc.astype(jnp.float32), start, axis=0)
    return x, y


def make(config: dict, key, seed: int, splits=("train",)) -> dict:
    """{"train": (x, y, mask, n)} as device arrays in packed row order:
    x (R, d), y and mask (R,), n (tasks,), R = sum(n)."""
    if tuple(splits) != ("train",):
        raise ValueError(f"mds_like makes the training split only, not {splits}")
    n = sizes(config)
    R, d = int(n.sum()), config["d"]
    kw, kr = jax.random.split(key)
    W = _weights(
        kw, config["tasks"], d, int(config["lexicon_frac"] * d), config["deviation"]
    )
    task = jnp.asarray(np.repeat(np.arange(n.shape[0], dtype=np.int32), n))
    rows = min(CHUNK, R)
    x = jnp.zeros((R, d), jnp.float32)
    y = jnp.zeros((R,), jnp.float32)
    for c in range(-(-R // rows)):
        # the last chunk ends at row R, redrawing rows of the one before it
        start = min(c * rows, R - rows)
        x, y = _fill(
            x, y, W, task, jax.random.fold_in(kr, c), start, rows, config["active"]
        )
    mask = jnp.ones((R,), jnp.float32)
    return {"train": (x, y, mask, jnp.asarray(n))}
