"""School-shaped regression tasks, made on the device from a seed.

A copy of ``repro.data.synthetic.school_like`` (paper section 7.1's School
set: 139 tasks, 27 features plus a bias, about 111 samples per task)
that draws in one jitted call on the device. Task weights come from a
3-cluster prior plus per-task noise; rows are standard normal with a bias
column appended and scaled to unit norm; targets are x.w plus noise.

The per-task sample counts are the same set for every seed (Poisson(111)
draws, at least 20, from the fixed ``size_seed``), dealt to the tasks in
an order drawn from the seed; each task's first round(0.75 n_i) samples
train and the rest test. So every seed has the same padded shapes and the
same total work, and only the order differs. The published set's skew
(22 to 251 students a school) is not reproduced: see the configuration's
``assumed``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def sizes(config: dict, seed: int):
    """(train counts, test counts) per task, as numpy int arrays."""
    base = np.random.RandomState(config["size_seed"]).poisson(
        config["n_avg"], config["tasks"]
    )
    n = np.maximum(config["n_min"], base)
    n = np.random.default_rng(seed).permutation(n)
    k = np.clip(np.round(config["train_frac"] * n).astype(int), 1, n - 1)
    return k, n - k


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _draw(key, tasks, d, n_train_max, n_test_max, n_train, n_test):
    kc, kk, kw, ktr, kte = jax.random.split(key, 5)
    centers = 1.5 * jax.random.normal(kc, (3, d + 1), jnp.float32)
    cluster = jax.random.randint(kk, (tasks,), 0, 3)
    w = centers[cluster] + 0.4 * jax.random.normal(kw, (tasks, d + 1), jnp.float32)

    def split(k, n_max, counts):
        k1, k2 = jax.random.split(k)
        x = jax.random.normal(k1, (tasks, n_max, d), jnp.float32)
        x = jnp.concatenate([x, jnp.ones((tasks, n_max, 1), jnp.float32)], axis=-1)
        x = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)
        y = jnp.einsum("mnd,md->mn", x, w, precision=jax.lax.Precision.HIGHEST)
        y = y + 0.35 * jax.random.normal(k2, (tasks, n_max), jnp.float32)
        mask = (jnp.arange(n_max)[None, :] < counts[:, None]).astype(jnp.float32)
        return x * mask[..., None], y * mask, mask, counts

    return split(ktr, n_train_max, n_train), split(kte, n_test_max, n_test)


def make(config: dict, key, seed: int, splits=("train",)) -> dict:
    """{split: (x, y, mask, n)} as device arrays, padded as
    ``repro.core.mtl_data.from_task_list`` pads them."""
    k_train, k_test = sizes(config, seed)
    train, test = _draw(
        key,
        config["tasks"],
        config["d"] - 1,
        int(k_train.max()),
        int(k_test.max()),
        jnp.asarray(k_train, jnp.int32),
        jnp.asarray(k_test, jnp.int32),
    )
    both = {"train": train, "test": test}
    return {s: both[s] for s in splits}
