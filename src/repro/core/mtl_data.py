"""Multi-task dataset containers: padded and packed.

Tasks have unequal sample counts n_i. ``MTLData`` pads every task to
``n_max`` and carries a validity mask, so that tasks vmap and shard as one
axis. Padded coordinates never get sampled by SDCA (indices are drawn in
[0, n_i)) and carry zero weight in all objective evaluations.

``PackedMTLData`` concatenates the tasks' rows with no per-task padding:
where task sizes are skewed (MDS: n_i from 314 to 20,751), the padded
layout stores mostly zeros. Task i's rows start at the exclusive cumulative
sum of n, and y, the mask and the dual variables follow the same row order.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class MTLData:
    """m tasks padded to a common n_max.

    x:    (m, n_max, d) float  features (phi already applied)
    y:    (m, n_max)    float  labels (+-1 classification / real regression)
    mask: (m, n_max)    float  1.0 on real samples, 0.0 on padding
    n:    (m,)          int32  true per-task sample counts
    """

    x: Array
    y: Array
    mask: Array
    n: Array

    layout = "padded"

    # -- pytree plumbing ----------------------------------------------------
    def tree_flatten(self):
        return (self.x, self.y, self.mask, self.n), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    # -- accessors ----------------------------------------------------------
    @property
    def m(self) -> int:
        return self.x.shape[0]

    @property
    def n_max(self) -> int:
        return self.x.shape[1]

    @property
    def d(self) -> int:
        return self.x.shape[2]

    # -- per-task reductions over the samples (core/dual.py) ----------------
    def task_sums(self, v: Array) -> Array:
        """(m,) per-task sums of a per-sample ``v``."""
        return jnp.sum(v, axis=1)

    def sum_over_n(self, v: Array) -> Array:
        """sum_i (1/n_i) sum_j v_j^i over every sample of ``v``."""
        return jnp.sum(v / self.n[:, None].astype(v.dtype))

    def task_xt(self, v: Array) -> Array:
        """(m, d): X_i^T v_[i] for every task."""
        return jnp.einsum("mnd,mn->md", self.x, v)

    def predictions(self, W: Array) -> Array:
        """w_i^T x_j^i for every sample, (m, n_max)."""
        return jnp.einsum("mnd,md->mn", self.x, W)

    def task(self, i: int) -> Tuple[Array, Array, int]:
        ni = int(self.n[i])
        return self.x[i, :ni], self.y[i, :ni], ni

    def pad_tasks(self, m_new: int) -> "MTLData":
        """Pad the task axis to ``m_new`` with empty (all-masked) tasks."""
        if m_new == self.m:
            return self
        assert m_new > self.m
        pad = m_new - self.m
        z = lambda a: jnp.concatenate(
            [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)], axis=0
        )
        # n=1 on padded tasks keeps 1/n_i finite; mask stays 0 so they are inert.
        n_pad = jnp.concatenate([self.n, jnp.ones((pad,), self.n.dtype)])
        return MTLData(z(self.x), z(self.y), z(self.mask), n_pad)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PackedMTLData:
    """m tasks packed row after row.

    x:    (R, d) float  every task's rows, task after task
    y:    (R,)   float  labels, in the rows' order
    mask: (R,)   float  1.0 on real rows, 0.0 on padding rows
    n:    (m,)   int32  per-task row counts
    n_max:   the largest n_i (static; read from ``n`` when not given)
    workers: the rows lie in ``workers`` equal blocks, block g holding tasks
             [g m / workers, (g + 1) m / workers) from its first row on and
             padding after them (``worker_layout``). A container built
             from task lists is one block.
    """

    x: Array
    y: Array
    mask: Array
    n: Array
    n_max: Optional[int] = None
    workers: int = 1

    layout = "packed"

    def __post_init__(self):
        if self.n_max is None:
            self.n_max = int(np.max(np.asarray(self.n)))

    def tree_flatten(self):
        return (self.x, self.y, self.mask, self.n), (self.n_max, self.workers)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def m(self) -> int:
        return self.n.shape[0]

    @property
    def d(self) -> int:
        return self.x.shape[1]

    def row_tasks(self) -> Array:
        """(R,) int32: the task of every row (of the block's last task on
        padding rows, which the mask zeroes)."""
        m_loc = self.m // self.workers
        rows = self.x.shape[0] // self.workers
        local = jax.vmap(lambda n: row_tasks(n, rows))(
            self.n.reshape(self.workers, m_loc)
        )
        first = jnp.arange(self.workers, dtype=jnp.int32)[:, None] * m_loc
        return (first + local).reshape(-1)

    # -- MTLData's per-task reductions, as segment reductions over the rows --
    def task_sums(self, v: Array) -> Array:
        return jax.ops.segment_sum(v, self.row_tasks(), num_segments=self.m)

    def sum_over_n(self, v: Array) -> Array:
        return jnp.sum(v / self.n[self.row_tasks()].astype(v.dtype))

    def task_xt(self, v: Array) -> Array:
        """One (m, R) x (R, d) product of the rows' one-hot task matrix."""
        tasks = jnp.arange(self.m, dtype=jnp.int32)[:, None]
        return jnp.where(self.row_tasks()[None, :] == tasks, v[None, :], 0) @ self.x

    def predictions(self, W: Array) -> Array:
        """(R,): each row's entry of X W^T."""
        z = self.x @ W.T  # (R, m)
        return jnp.take_along_axis(z, self.row_tasks()[:, None], axis=1)[:, 0]


def refuse_packed(data, what: str) -> None:
    """Raise where ``what`` runs on the padded layout only."""
    if isinstance(data, PackedMTLData):
        raise ValueError(
            f"{what} runs on padded task storage (MTLData); packed task "
            f'storage trains through engine="distributed" with solver '
            f'"naive" or "block_gram"'
        )


def row_tasks(n: Array, rows: int) -> Array:
    """Task of each of ``rows`` packed rows holding tasks of counts ``n``;
    rows past sum(n) get the last task."""
    ends = jnp.cumsum(n)
    t = jnp.searchsorted(ends, jnp.arange(rows, dtype=ends.dtype), side="right")
    return jnp.minimum(t, n.shape[0] - 1).astype(jnp.int32)


def worker_layout(n: np.ndarray, workers: int) -> Tuple[np.ndarray, int, np.ndarray]:
    """Where packed rows lie when tasks are dealt to ``workers`` in
    contiguous ranges: (dst, rows per worker, padded n).

    The task axis is padded to a multiple of ``workers`` with empty tasks
    of one zero row each (n = 1 keeps 1/n_i finite; their mask is 0), and
    every worker's rows are padded to the largest worker's row total.
    ``dst[k]`` is the position of the k-th real row in the sharded array.
    """
    n = np.asarray(n, np.int64)
    m_pad = -(-n.shape[0] // workers) * workers
    n_pad = np.concatenate([n, np.ones(m_pad - n.shape[0], np.int64)])
    per = n_pad.reshape(workers, -1)
    rows = int(per.sum(axis=1).max())
    starts = (np.arange(workers)[:, None] * rows + np.cumsum(per, axis=1) - per).reshape(-1)
    shift = starts[: n.shape[0]] - (np.cumsum(n) - n)  # raw start -> sharded start
    dst = np.arange(int(n.sum())) + np.repeat(shift, n)
    return dst, rows, n_pad.astype(np.int32)


def pack_tasks(xs: Sequence[np.ndarray], ys: Sequence[np.ndarray]) -> PackedMTLData:
    """Build PackedMTLData from per-task (n_i, d) / (n_i,) numpy arrays."""
    assert len(xs) == len(ys) and len(xs) > 0
    ns = [int(x.shape[0]) for x in xs]
    x = np.concatenate([np.asarray(a, np.float32) for a in xs])
    y = np.concatenate([np.asarray(b, np.float32).reshape(-1) for b in ys])
    return PackedMTLData(
        jnp.asarray(x), jnp.asarray(y), jnp.ones(x.shape[0], jnp.float32),
        jnp.asarray(ns, jnp.int32), max(ns),
    )


def from_task_list(
    xs: Sequence[np.ndarray], ys: Sequence[np.ndarray], n_max: int | None = None
) -> MTLData:
    """Build padded MTLData from per-task (n_i, d) / (n_i,) numpy arrays."""
    m = len(xs)
    assert m == len(ys) and m > 0
    d = xs[0].shape[1]
    ns = [int(x.shape[0]) for x in xs]
    n_max = n_max or max(ns)
    X = np.zeros((m, n_max, d), np.float32)
    Y = np.zeros((m, n_max), np.float32)
    M = np.zeros((m, n_max), np.float32)
    for i, (x, y) in enumerate(zip(xs, ys)):
        ni = ns[i]
        assert ni <= n_max, f"task {i} has {ni} > n_max={n_max}"
        X[i, :ni] = x
        Y[i, :ni] = np.asarray(y).reshape(-1)
        M[i, :ni] = 1.0
    return MTLData(
        jnp.asarray(X), jnp.asarray(Y), jnp.asarray(M), jnp.asarray(ns, jnp.int32)
    )


def normalize_rows(data: MTLData, max_norm: float = 1.0) -> MTLData:
    """Scale every sample to ||x|| <= max_norm (the theory in Lemma 7 assumes
    normalized features; the algorithm itself does not require it)."""
    norms = jnp.linalg.norm(data.x, axis=-1, keepdims=True)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norms, 1e-12))
    return MTLData(data.x * scale, data.y, data.mask, data.n)


def train_test_split_tasks(
    xs: List[np.ndarray],
    ys: List[np.ndarray],
    frac_train: float,
    seed: int,
) -> Tuple[List[np.ndarray], List[np.ndarray], List[np.ndarray], List[np.ndarray]]:
    rng = np.random.RandomState(seed)
    xtr, ytr, xte, yte = [], [], [], []
    for x, y in zip(xs, ys):
        n = x.shape[0]
        perm = rng.permutation(n)
        k = max(1, int(round(frac_train * n)))
        k = min(k, n - 1) if n > 1 else 1
        tr, te = perm[:k], perm[k:]
        xtr.append(x[tr]), ytr.append(y[tr])
        xte.append(x[te]), yte.append(y[te])
    return xtr, ytr, xte, yte
