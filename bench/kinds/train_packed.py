"""Training cells on packed task storage: back-to-back complete fits of
``DMTRLEstimator(engine="distributed", mesh=...)`` on a
``repro.core.mtl_data.PackedMTLData``, at a fixed schedule.

The window and ``fit_s`` are those of ``bench/kinds/train.py``, whose
schedule, H, readings, estimator and fit result this kind shares; the data
are the generator's packed rows, and ``correct`` compares every fit of the
window with the plain reference over packed rows
(``bench/reference/dmtrl_packed.py``), run after the window.

A program without packed task storage fails here at once, before any data
is made.
"""
from __future__ import annotations

import time

import numpy as np

from bench.kinds.train import build, fit_result, local_iters, readings


def reference(cell, config, raw, seed, dtype=None):
    """The plain reference's fit of the cell (float32 unless ``dtype``)."""
    import jax.numpy as jnp

    from bench.lib.seeds import fit_seed
    from bench.reference import dmtrl_packed

    x, y, _, n = raw
    n = np.asarray(n)
    return dmtrl_packed.fit(
        x, y, n,
        loss=config["loss"], lam=config["lam"], eta=config["eta"],
        outer_iters=cell["outer_iters"], rounds=cell["rounds"],
        H=local_iters(cell, int(n.max())), seed=fit_seed(seed),
        jitter=config["omega_jitter"],
        dtype=jnp.float32 if dtype is None else dtype,
    )


def run(ctx) -> dict:
    from repro.core.mtl_data import PackedMTLData  # a program without it fails here

    cell, config = ctx.cell, ctx.config
    raw = ctx.generator.make(config, ctx.key, ctx.seed, ("train",))["train"]
    data = PackedMTLData(*raw)
    est = build(ctx, data)
    est.fit(data)  # warm-up: compiles every program the window runs
    ctx.setup_done()

    fits = []
    with ctx.window() as win:
        while win.elapsed() < ctx.seconds:
            with ctx.span("fit"):
                est.fit(data)
            fits.append(fit_result(est))
    ctx.read_memory()
    del est

    stored_rows, d = data.x.shape
    H = local_iters(cell, data.n_max)
    samples = int(np.asarray(data.n).sum())
    rounds = len(fits) * cell["outer_iters"] * cell["rounds"]
    gaps = [g for _, g in fits]
    ctx.info(
        fits=len(fits), rounds=rounds, tasks=data.m, d=d, n_max=data.n_max, H=H,
        samples=samples, stored_rows=stored_rows,
        gap_first=float(gaps[0][0]), gap_last=float(gaps[0][-1]),
    )

    t_ref = time.perf_counter()
    ref = reference(cell, config, raw, ctx.seed)
    checks = readings(fits, ref, cell["rounds"])
    ctx.info(reference_s=time.perf_counter() - t_ref, **checks)
    return {
        "e2e": {"fit_s": (win.seconds / len(fits), "s")},
        "attempted": len(fits),
        "failed": 0,
        "checks": {k: (v, cell["limits"][k]) for k, v in checks.items()},
        "counters": {
            "fits": len(fits),
            "rounds": rounds,
            "tasks": int(config["tasks"]),
            "d": d,
            "H": H,
            "samples": samples,
            "stored_rows": stored_rows,
        },
    }
