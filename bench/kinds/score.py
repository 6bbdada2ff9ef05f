"""Scoring cells: ``est.serving_scheduler(batch=...)`` under open-loop
Poisson traffic, with one hot-swap at the window's midpoint.

Set-up makes the data on the device from the seed, fits the served model
(version 1) and, on a second estimator, a fit and a ``partial_fit`` whose W
is published at the midpoint; warms the scorer's tile (``warmup()`` and
one tile) and builds every request. The window sends each request when it
is due, by the host's clock, from one thread that also drives the
scheduler; it ends when the last request has completed. Each request's
latency runs from its due time, not from when it was sent, so a late
generator or a long tile counts.

``correct`` compares every served score with the float64 dot product of
the request's row and the W that the benchmark handed to the scheduler for
the version the request records.
"""
from __future__ import annotations

import time

import numpy as np


def fit_params(cell: dict, config: dict) -> dict:
    fit = cell["fit"]
    return dict(
        loss=config["loss"], lam=config["lam"], eta=config["eta"],
        outer_iters=fit["outer_iters"], rounds=fit["rounds"],
        omega_jitter=config["omega_jitter"],
    )


def serve(sched, reqs, due, clock, span, swap_at=None, swap=None):
    """Send ``reqs[i]`` at ``due[i]`` (absolute, on ``clock``) and step the
    scheduler until every request has completed. ``swap()`` runs once, at
    the first loop turn at or after ``swap_at``. Returns the backlog (queued
    requests) right after the last request was sent."""
    n, i = len(reqs), 0
    swapped = swap is None
    backlog = None
    while True:
        now = clock()
        while i < n and due[i] <= now:
            sched.submit(reqs[i])
            i += 1
            if i == n:
                backlog = sched.pending
        if not swapped and now >= swap_at:
            with span("publish"):
                swap()
            swapped = True
        if sched.pending:
            with span("tile"):
                sched.step()
        elif i >= n:
            return backlog
        else:
            with span("wait"):
                while clock() < due[i]:
                    pass


def make_requests(x_test, tasks, rows):
    from repro.serve.mtl import ScoreRequest

    return [
        ScoreRequest(task=int(t), x=x_test[t, j]) for t, j in zip(tasks, rows)
    ]


def readings(reqs, x_test, tasks, rows, weights) -> dict:
    """score_err: the largest |served - w.x| / (|w| |x|) over the served
    requests, with w the row of the W handed over for the version the
    request records (float64); unserved: requests that never completed;
    unknown_version and versions_missing: served versions that were never
    handed over, and handed-over versions that served nothing."""
    done = [r.status == "done" for r in reqs]
    versions = np.asarray([r.snapshot_version if d else -1 for r, d in zip(reqs, done)])
    scores = np.asarray([r.score if d else np.nan for r, d in zip(reqs, done)], np.float64)
    ok = np.asarray(done)
    X = np.asarray(x_test, np.float64)[tasks, rows]
    err = np.zeros(len(reqs))
    unknown = 0
    for v in np.unique(versions[ok]):
        sel = ok & (versions == v)
        if v not in weights:
            unknown += int(sel.sum())
            continue
        w = weights[v][tasks[sel]]
        z = np.einsum("nd,nd->n", w, X[sel])
        scale = np.maximum(np.linalg.norm(w, axis=1) * np.linalg.norm(X[sel], axis=1), 1e-30)
        err[sel] = np.abs(scores[sel] - z) / scale
    served = set(int(v) for v in np.unique(versions[ok]))
    return {
        "score_err": float(err[ok].max()) if ok.any() else float("inf"),
        "unserved": int((~ok).sum()),
        "unknown_version": unknown,
        "versions_missing": len(set(weights) - served),
    }


def setup(ctx) -> dict:
    """The served scheduler, warmed, and what the window and the check
    need: the test rows, the W of each version and the W to publish."""
    from repro.core import DMTRLEstimator
    from repro.core.mtl_data import MTLData
    from repro.launch.mesh import make_mesh

    from bench.lib.seeds import fit_seed

    cell, config = ctx.cell, ctx.config
    splits = ctx.generator.make(config, ctx.key, ctx.seed, ("train", "test"))
    train = MTLData(*splits["train"])
    x_test = np.asarray(splits["test"][0])
    n_test = np.asarray(splits["test"][3])

    mesh = make_mesh((1,), ("data",), devices=ctx.devices[:1])
    params = fit_params(cell, config)
    est = DMTRLEstimator(engine="distributed", mesh=mesh, seed=fit_seed(ctx.seed), **params)
    est.fit(train)
    second = DMTRLEstimator(engine="distributed", mesh=mesh, seed=fit_seed(ctx.seed), **params)
    second.fit(train).partial_fit(train)

    sched = est.serving_scheduler(batch=cell["batch"], clock=time.perf_counter)
    sched.engine.warmup()
    warm = make_requests(x_test, np.zeros(cell["batch"], int), np.zeros(cell["batch"], int))
    for r in warm:
        sched.submit(r)
    sched.run_until_idle()
    return {
        "est": est,
        "sched": sched,
        "x_test": x_test,
        "n_test": n_test,
        "weights": {sched.version: np.array(est.W_, np.float64)},
        "W2": np.array(second.W_, np.float32),
    }


def window(ctx, st: dict, traffic: dict):
    """One window of open-loop traffic with the hot-swap at its midpoint.
    Returns (requests, due times, tasks, rows, window start, backlog)."""
    from bench.lib import traffic as traffic_mod

    sched, W2, weights = st["sched"], st["W2"], st["weights"]
    due, tasks, rows = traffic_mod.open_loop(traffic, ctx.seed, ctx.seconds, st["n_test"])
    reqs = make_requests(st["x_test"], tasks, rows)
    ctx.setup_done()

    def swap():
        weights[sched.publish_weights(W2)] = W2.astype(np.float64)

    clock = time.perf_counter
    with ctx.window():
        t0 = clock()
        backlog = serve(sched, reqs, t0 + due, clock, ctx.span, t0 + 0.5 * ctx.seconds, swap)
    return reqs, due, tasks, rows, t0, backlog


def summary(ctx, reqs, due, t0, backlog) -> tuple:
    """(latency of every request in seconds, inf where it never completed;
    the info fields of the window)."""
    done = [r for r in reqs if r.status == "done"]
    # a request that never completed misses any limit: its latency is inf
    lat = np.asarray([
        r.finish_s - (t0 + d) if r.status == "done" else np.inf
        for r, d in zip(reqs, due)
    ])
    late = np.asarray([r.arrival_s - (t0 + d) for r, d in zip(reqs, due) if r.arrival_s is not None])
    win = ctx.win.seconds
    info = dict(
        offered=len(reqs), completed=len(done), window_s=win,
        offered_per_s=len(reqs) / ctx.seconds, completed_per_s=len(done) / win,
        lateness_p50_ms=float(np.percentile(late, 50)) * 1e3,
        lateness_max_ms=float(late.max()) * 1e3,
        latency_p50_ms=float(np.percentile(lat, 50, method="higher")) * 1e3,
        latency_p99_ms=float(np.percentile(lat, 99, method="higher")) * 1e3,
        versions_served=sorted({int(r.snapshot_version) for r in done}),
        backlog_at_last_send=backlog,
    )
    return lat, info


def run(ctx) -> dict:
    cell = ctx.cell
    st = setup(ctx)
    sched = st["sched"]
    filled0, slots0 = sched.metrics.tile_filled, sched.metrics.tile_slots
    tiles0 = sched.metrics.tiles
    reqs, due, tasks, rows, t0, backlog = window(ctx, st, cell["traffic"])
    ctx.read_memory()
    lat, info = summary(ctx, reqs, due, t0, backlog)
    ctx.info(tiles=sched.metrics.tiles - tiles0, **info)
    checks = readings(reqs, st["x_test"], tasks, rows, st["weights"])
    return {
        "e2e": {"score_p99_ms": (info["latency_p99_ms"], "ms")},
        "attempted": len(reqs),
        "failed": len(reqs) - info["completed"],
        "checks": {k: (v, cell["limits"][k]) for k, v in checks.items()},
        "counters": {
            "tile_filled": sched.metrics.tile_filled - filled0,
            "tile_slots": sched.metrics.tile_slots - slots0,
        },
    }
