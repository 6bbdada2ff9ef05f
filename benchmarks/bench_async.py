"""Sync vs async rounds-to-gap under simulated stragglers.

For each straggler severity the bulk-synchronous engine pays
``max(delays)`` ticks per round (every round barriers on the slowest
worker), while the bounded-staleness engine keeps the fast workers
committing. The headline metric is *ticks to reach a target duality gap*
on the shared simulated clock.

    PYTHONPATH=src python -m benchmarks.bench_async
    PYTHONPATH=src python -m benchmarks.bench_async --devices 4 --tau 1 2 4
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def run(n_dev: int, taus, straggler: int, seed: int = 0):
    from repro.core import AsyncOptions, DMTRLConfig, MeshAxes
    from repro.core.async_dmtrl import fit_async
    from repro.core.distributed import fit_distributed
    from repro.launch.mesh import make_mesh
    from repro.core import convergence as cv
    from repro.data.synthetic import synthetic
    sp = synthetic(1, m=n_dev, d=32, n_train_avg=80, n_test_avg=20, seed=2)
    delays = (1,) * (n_dev - 1) + (straggler,)
    base = dict(
        loss="hinge", lam=1e-4, outer_iters=2, rounds=8, local_iters=64,
        solver="block_gram", block_size=32, seed=seed,
    )
    mesh = make_mesh((n_dev,), ("data",))
    ax = MeshAxes(data="data")

    _, _, _, h_sync = fit_distributed(DMTRLConfig(**base), sp.train, mesh, ax)
    sync_ticks = cv.sync_effective_ticks(h_sync, delays)
    target = 1.5 * float(h_sync["gap"][-1])
    rows = [
        {
            "engine": "sync",
            "tau": 0,
            "straggler": straggler,
            "final_gap": float(h_sync["gap"][-1]),
            "gap_target": target,
            "ticks_total": float(sync_ticks[-1]),
            "ticks_to_target": cv.ticks_to_gap(sync_ticks, h_sync["gap"], target),
            "max_staleness": 0,
        }
    ]
    for tau in taus:
        opts = AsyncOptions(tau=tau, async_delays=delays)
        _, _, _, h = fit_async(DMTRLConfig(**base), sp.train, mesh, ax,
                               options=opts)
        ticks, gaps = cv.effective_gap_curve(h)
        s = cv.staleness_summary(h)
        rows.append(
            {
                "engine": "async",
                "tau": tau,
                "straggler": straggler,
                "final_gap": float(gaps[-1]),
                "gap_target": target,
                "ticks_total": float(ticks[-1]),
                "ticks_to_target": cv.ticks_to_gap(ticks, gaps, target),
                "max_staleness": s["max_staleness"],
            }
        )
    return rows, target


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--tau", type=int, nargs="+", default=[1, 2, 4])
    ap.add_argument("--straggler", type=int, nargs="+", default=[2, 4])
    args = ap.parse_args()

    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={args.devices}"
    )
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

    all_rows = []
    print("engine,tau,straggler,final_gap,ticks_total,ticks_to_target,max_staleness")
    for s in args.straggler:
        rows, _ = run(args.devices, args.tau, s)
        for r in rows:
            print(
                f"{r['engine']},{r['tau']},{r['straggler']},{r['final_gap']:.5f},"
                f"{r['ticks_total']:.0f},{r['ticks_to_target']:.0f},"
                f"{r['max_staleness']}",
                flush=True,
            )
        all_rows.extend(rows)
    os.makedirs("results", exist_ok=True)
    with open("results/bench_async.json", "w") as f:
        json.dump(all_rows, f, indent=1)


if __name__ == "__main__":
    main()
