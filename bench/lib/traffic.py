"""Open-loop request traffic from a seed.

The task draw is copied from ``benchmarks/bench_fleet.py`` (``_zipf_tasks``:
p_k proportional to 1/(k+1)^a over the task ids) and the arrivals are its
exponential inter-arrival gaps; the virtual clock that bench advanced by
CPU tile times is not copied, the requests are sent on the host's clock.

Every seed sends the same number of requests, ``rate * seconds``, with the
same set of gaps (drawn once from ``gap_seed``, scaled so the last request
is due at ``seconds``) in an order drawn from the seed: seeds differ in
where the bursts fall, not in how much work there is.
"""
from __future__ import annotations

import numpy as np


def zipf_tasks(rng, n: int, tasks: int, a: float):
    p = 1.0 / np.arange(1, tasks + 1) ** a
    return rng.choice(tasks, size=n, p=p / p.sum())


def open_loop(traffic: dict, seed: int, seconds: float, rows_per_task):
    """(due times in seconds from the window's start, task ids, row index
    within each task's test rows) for ``rate * seconds`` requests."""
    rate = float(traffic["rate"])
    n = int(round(rate * seconds))
    gaps = np.random.default_rng(traffic["gap_seed"]).exponential(1.0 / rate, n)
    rng = np.random.default_rng(seed)
    gaps = gaps[rng.permutation(n)]
    due = np.cumsum(gaps) * (seconds / gaps.sum())
    tasks = zipf_tasks(rng, n, len(rows_per_task), traffic["zipf_a"])
    rows = np.floor(rng.random(n) * np.asarray(rows_per_task)[tasks]).astype(np.int64)
    return due, tasks, rows
