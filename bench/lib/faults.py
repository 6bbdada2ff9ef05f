"""Faults planted in the program under test: each breaks the timed path in
one way a cell can be broken. ``bench/tests/test_faults.py`` sees
``correct`` come out false with each; ``bench/tools/readings.py --faults``
reads on the chip, at a cell's own size, how far each moves the numbers
that ``correct`` compares.

  unchanged    every round returns alpha and W as they were;
  half         each round drops the coordinate updates of the second half
               of every task's samples and doubles the rest's delta-b;
  no_exchange  the server reduce skips the all_gather, so each worker
               applies only its own tasks' delta-b (a fault only where
               tasks lie on more than one device);
  altered      the scorer writes a wrong score into one request per tile.

``plant(name, patch)`` replaces a function of the program through
``patch(owner, attribute, value)``, such as pytest's
``monkeypatch.setattr``; ``planted(name)`` does the same for the length of
a ``with`` block.
"""
from __future__ import annotations

import contextlib


def _unchanged(patch):
    import jax
    from repro.core import distributed

    def make_round(*a, **k):
        return jax.jit(lambda x, y, mask, n, alpha, W, sigma, key: (alpha, W))

    patch(distributed, "make_distributed_round", make_round)


def _half(patch):
    import jax.numpy as jnp
    from repro.core import distributed

    orig = distributed.make_local_solve

    def make_local_solve(cfg, *a, **k):
        solve = orig(cfg, *a, **k)

        def half(x, y, n, alpha, W_read, sigma_rows, key):
            dalpha, _ = solve(x, y, n, alpha, W_read, sigma_rows, key)
            keep = (jnp.arange(x.shape[1]) < (n[:, None] + 1) // 2).astype(x.dtype)
            dalpha = dalpha * keep
            r = jnp.einsum("mnd,mn->md", x, dalpha)
            db = 2.0 * cfg.eta * r / jnp.maximum(n, 1)[:, None].astype(r.dtype)
            return dalpha, db

        return half

    patch(distributed, "make_local_solve", make_local_solve)


def _no_exchange(patch):
    import jax
    from repro.core import distributed

    def server_reduce(cfg, axes, sigma_rows, db):
        m_loc = db.shape[0]
        di = jax.lax.axis_index(axes.data)
        own = jax.lax.dynamic_slice_in_dim(sigma_rows, di * m_loc, m_loc, axis=1)
        return own @ db / cfg.lam

    patch(distributed, "server_reduce", server_reduce)


def _altered(patch):
    from repro.serve.mtl import MTLScoringEngine

    orig = MTLScoringEngine._write_back

    def write_back(self, requests, z):
        z = z.copy()
        z[0] += 0.01
        return orig(self, requests, z)

    patch(MTLScoringEngine, "_write_back", write_back)


FAULTS = {
    "unchanged": _unchanged,
    "half": _half,
    "no_exchange": _no_exchange,
    "altered": _altered,
}


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
