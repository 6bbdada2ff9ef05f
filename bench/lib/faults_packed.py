"""Faults planted in the program's packed path (bench/lib/faults.py plants
those of the padded one): each breaks the timed path of a packed cell in
one way it can be broken.

  unchanged    every round returns alpha and W as they were (the padded
               path's fault: the packed round is built by the same
               ``make_distributed_round``);
  half         each round drops the coordinate updates of the second half
               of every task's samples and doubles the rest's delta-b.

``plant`` and ``planted`` are those of bench/lib/faults.py, over this
table.
"""
from __future__ import annotations

import contextlib

from bench.lib import faults


def _half(patch):
    import jax.numpy as jnp
    from repro.core import distributed
    from repro.core.mtl_data import row_tasks

    orig = distributed.make_local_solve

    def make_local_solve(cfg, *a, **k):
        solve = orig(cfg, *a, **k)
        if not k.get("packed"):
            return solve

        def half(x, y, n, alpha, W_read, sigma, key):
            dalpha, _ = solve(x, y, n, alpha, W_read, sigma, key)
            t = row_tasks(n, x.shape[0])
            j = jnp.arange(x.shape[0]) - (jnp.cumsum(n) - n)[t]
            dalpha = dalpha * (j < (n[t] + 1) // 2).astype(x.dtype)
            tasks = jnp.arange(n.shape[0], dtype=t.dtype)[:, None]
            r = jnp.where(t[None, :] == tasks, dalpha[None, :], 0) @ x
            db = 2.0 * cfg.eta * r / jnp.maximum(n, 1)[:, None].astype(r.dtype)
            return dalpha, db

        return half

    patch(distributed, "make_local_solve", make_local_solve)


FAULTS = {"unchanged": faults.FAULTS["unchanged"], "half": _half}


def plant(name: str, patch) -> None:
    FAULTS[name](patch)


@contextlib.contextmanager
def planted(name: str):
    saved = []

    def patch(owner, attr, value):
        saved.append((owner, attr, getattr(owner, attr)))
        setattr(owner, attr, value)

    try:
        plant(name, patch)
        yield
    finally:
        for owner, attr, value in reversed(saved):
            setattr(owner, attr, value)
