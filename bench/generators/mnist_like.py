"""MNIST-shaped one-vs-all tasks, made on the device from a seed.

A copy of ``repro.data.synthetic.mnist_like`` (paper section 7.1's MNIST
setting: 10 one-vs-all tasks over d = 784) that draws in one jitted call
on the device instead of on the host. Digits are class templates of 3 to
6 Gaussian blobs on the 28 x 28 grid plus uniform pixel noise; task c has
half positives (template c) and half negatives (another class's
template); 3% of the labels are flipped; rows are scaled to unit norm.
The rows are not permuted: SDCA samples coordinates uniformly, so the
order carries no information.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, tasks: int, d: int, n: int):
    side = int(round(d**0.5))
    kc, kn, kx, kf = jax.random.split(key, 4)
    max_blobs = 6
    blobs = 3 + jnp.arange(tasks) % 4
    centers = jax.random.randint(kc, (tasks, max_blobs, 2), 4, side - 4)
    grid = jnp.arange(side, dtype=jnp.float32)
    cx = centers[..., 0, None, None].astype(jnp.float32)
    cy = centers[..., 1, None, None].astype(jnp.float32)
    g = jnp.exp(
        -((grid[None, None, None, :] - cx) ** 2 + (grid[None, None, :, None] - cy) ** 2)
        / (2.0 * 2.5**2)
    )
    g = g * (jnp.arange(max_blobs)[None, :] < blobs[:, None])[..., None, None]
    img = g.sum(axis=1).reshape(tasks, d)
    tmpl = img / jnp.maximum(img.max(axis=1, keepdims=True), 1e-6)

    half = n // 2
    other = (
        jnp.arange(tasks)[:, None]
        + jax.random.randint(kn, (tasks, n - half), 1, tasks)
    ) % tasks
    base = jnp.concatenate(
        [jnp.broadcast_to(tmpl[:, None, :], (tasks, half, d)), tmpl[other]], axis=1
    )
    x = base + 0.55 * jax.random.uniform(kx, (tasks, n, d), jnp.float32)
    x = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)
    y = jnp.concatenate(
        [jnp.ones((tasks, half), jnp.float32), -jnp.ones((tasks, n - half), jnp.float32)],
        axis=1,
    )
    y = jnp.where(jax.random.uniform(kf, (tasks, n)) < 0.03, -y, y)
    mask = jnp.ones((tasks, n), jnp.float32)
    counts = jnp.full((tasks,), n, jnp.int32)
    return x, y, mask, counts


def make(config: dict, key, seed: int, splits=("train",)) -> dict:
    """{split: (x, y, mask, n)} as device arrays, padded as
    ``repro.core.mtl_data.from_task_list`` pads them."""
    out = {}
    for i, split in enumerate(splits):
        n = config[f"n_per_task_{split}"]
        out[split] = _draw(
            jax.random.fold_in(key, i), config["tasks"], config["d"], n
        )
    return out
