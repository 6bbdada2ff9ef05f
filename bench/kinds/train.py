"""Training cells: back-to-back complete fits of
``DMTRLEstimator(engine="distributed", mesh=...)`` at a fixed schedule.

Set-up makes the data on the device from the seed, builds the estimator
once and runs one whole fit, which compiles every program the window
uses (the window's fits are the same computation). The window starts
fits until ``--seconds`` have passed and ends when the last one returns,
so it holds whole fits only: ``fit_s`` is the window over the fits.

``correct`` compares what the window's fits produced, at the cell's full
size, with the plain reference (``bench/reference/dmtrl.py``) run after the
window: W after the last Omega-step, and the duality gap after every
round, for every fit of the window.
"""
from __future__ import annotations

import time

import numpy as np


def schedule(cell: dict, config: dict) -> dict:
    """The estimator's core parameters for this cell."""
    return dict(
        loss=config["loss"],
        lam=config["lam"],
        eta=config["eta"],
        outer_iters=cell["outer_iters"],
        rounds=cell["rounds"],
        local_iters=0,
        block_size=cell["block_size"],
        solver=cell["solver"],
        omega_jitter=config["omega_jitter"],
    )


def local_iters(cell: dict, n_max: int) -> int:
    """H: one pass over the padded task (local_iters = 0 means n_max),
    rounded up to a whole number of blocks."""
    b = cell["block_size"]
    return -(-n_max // b) * b


def readings(fits, ref, rounds: int) -> dict:
    """The numbers ``correct`` compares, worst over the fits.

    w_err: max |W - W_ref| over max |W_ref|, W after the last Omega-step.
    gap_err: max over all rounds of |gap - gap_ref| over |primal_ref|: the
    gap is a difference of two near objectives, so it is measured against
    the size of the objective and not against itself.
    gap1_err: the same over the first W-step's ``rounds`` rounds alone,
    before any Omega-step.
    """
    w_ref = ref["W"]
    gap_ref = ref["primal"] - ref["dual"]
    scale = np.maximum(np.abs(ref["primal"]), 1e-30)
    out = {"w_err": 0.0, "gap_err": 0.0, "gap1_err": 0.0}
    for W, gap in fits:
        w = float(np.max(np.abs(W - w_ref)) / np.max(np.abs(w_ref)))
        if gap.shape != gap_ref.shape:
            e = np.full(gap_ref.shape, np.inf)
        else:
            e = np.abs(gap - gap_ref) / scale
        for k, v in (("w_err", w), ("gap_err", np.max(e)), ("gap1_err", np.max(e[:rounds]))):
            # NaN compares false with every limit: read it as infinite
            out[k] = max(out[k], float(v)) if np.isfinite(v) else float("inf")
    return out


def reference(cell, config, data, seed, dtype=None):
    """The plain reference's fit of the cell (float32 unless ``dtype``)."""
    import jax.numpy as jnp

    from bench.lib.seeds import fit_seed
    from bench.reference import dmtrl

    x, y, mask, n = data
    return dmtrl.fit(
        x, y, mask, n,
        loss=config["loss"], lam=config["lam"], eta=config["eta"],
        outer_iters=cell["outer_iters"], rounds=cell["rounds"],
        H=local_iters(cell, x.shape[1]), seed=fit_seed(seed),
        jitter=config["omega_jitter"],
        dtype=jnp.float32 if dtype is None else dtype,
    )


def build(ctx, data):
    """The estimator the window drives, over a mesh of the cell's chips."""
    from repro.core import DMTRLEstimator
    from repro.launch.mesh import make_mesh

    from bench.lib.seeds import fit_seed

    mesh = make_mesh((ctx.chips,), ("data",), devices=ctx.devices[: ctx.chips])
    params = schedule(ctx.cell, ctx.config)
    return DMTRLEstimator(
        engine="distributed", mesh=mesh, seed=fit_seed(ctx.seed), **params
    )


def fit_result(est):
    return np.asarray(est.W_, np.float64), np.asarray(est.history_["gap"], np.float64)


def run(ctx) -> dict:
    from repro.core.mtl_data import MTLData

    cell, config = ctx.cell, ctx.config
    raw = ctx.generator.make(config, ctx.key, ctx.seed, ("train",))["train"]
    data = MTLData(*raw)
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

    m, n_max, d = data.x.shape
    H = local_iters(cell, n_max)
    samples = int(np.asarray(data.n).sum())
    rounds = len(fits) * cell["outer_iters"] * cell["rounds"]
    gaps = [g for _, g in fits]
    ctx.info(
        fits=len(fits), rounds=rounds, shape=[m, n_max, d], H=H, samples=samples,
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
        },
    }
