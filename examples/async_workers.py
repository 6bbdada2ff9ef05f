"""Bounded-staleness DMTRL with a straggler worker, via the estimator.

8 simulated workers (host devices), one of them 4x slower. The synchronous
engine barriers every round on the straggler; the async engine (tau > 0)
lets the fast workers keep committing against bounded-stale snapshots, so
the duality gap falls much earlier on the simulated wall clock.

Install the package once (``pip install -e .``) or export
``PYTHONPATH=src``, then:

    python examples/async_workers.py
    python examples/async_workers.py --trace out.json   # span tracing on
    python examples/async_workers.py --tiny             # CI smoke schedule

With ``--trace`` the threaded and gossip runs execute under the ``obs``
span tracer and the whole run is exported as Chrome-trace JSON — open
``chrome://tracing`` (or https://ui.perfetto.dev) and load the file to
see every worker thread's gate/snapshot/solve/commit timeline nested
under its rounds, plus the driver's W-step/Omega-step alternation.
"""
import argparse
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

from repro import obs
from repro.core import AsyncOptions, DMTRLEstimator, MeshAxes
from repro.core import convergence as cv
from repro.data.synthetic import synthetic
from repro.launch.mesh import make_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--trace", metavar="OUT.json", default=None,
        help="enable span tracing and write a Chrome-trace JSON here",
    )
    ap.add_argument(
        "--tiny", action="store_true",
        help="short schedule (CI examples-smoke)",
    )
    args = ap.parse_args()

    n_dev = len(jax.devices())
    print(f"devices: {n_dev} (each = one worker group)")
    sp = synthetic(1, m=8, d=48, n_train_avg=120, n_test_avg=40, seed=0)
    delays = (1,) * (n_dev - 1) + (4,)  # last worker is a 4x straggler

    base = dict(
        loss="hinge", lam=1e-4, outer_iters=2,
        rounds=3 if args.tiny else 8,
        local_iters=32 if args.tiny else 128, seed=0,
    )
    mesh = make_mesh((n_dev,), ("data",))
    ax = MeshAxes(data="data")

    print("synchronous (every round barriers on the straggler)...")
    sync = DMTRLEstimator(
        engine="distributed", mesh=mesh, axes=ax, **base
    ).fit(sp.train)
    sync_ticks = cv.sync_effective_ticks(sync.history, delays)

    print("async, tau=2, deterministic straggler schedule...")
    anc = DMTRLEstimator(
        engine="async", mesh=mesh, axes=ax,
        async_options=AsyncOptions(tau=2, async_delays=delays), **base
    ).fit(sp.train)
    a_ticks, a_gaps = cv.effective_gap_curve(anc.history)

    target = 2.0 * sync.history["gap"][-1]
    t_sync = cv.ticks_to_gap(sync_ticks, sync.history["gap"], target)
    t_async = cv.ticks_to_gap(a_ticks, a_gaps, target)
    print(f"  final gap      sync {sync.history['gap'][-1]:.4f}  async {a_gaps[-1]:.4f}")
    print(f"  ticks to gap<={target:.4f}:  sync {t_sync:.0f}  async {t_async:.0f}")
    s = cv.staleness_summary(anc.history)
    print(
        f"  staleness: max {s['max_staleness']:.0f} commits, "
        f"mean {s['mean_staleness']:.2f}, max lag {s['max_lag']:.0f} rounds"
    )

    # from here on the transports are REAL (worker threads): turn the span
    # tracer on so the runs land in the Chrome trace when --trace is given
    if args.trace:
        obs.enable(clear=True)

    # same protocol, different substrate: a REAL in-host parameter server
    # (worker threads, lock-protected versioned state, nondeterministic
    # arrival order). No mesh needed — the transport owns the workers.
    print("async, tau=2, threaded transport (real parameter server)...")
    thr = DMTRLEstimator(
        engine="async",
        async_options=AsyncOptions(
            tau=2, async_delays=delays, transport="threaded", n_workers=n_dev
        ),
        **base,
    ).fit(sp.train)
    st = cv.staleness_summary(thr.history)
    print(
        f"  final gap {thr.history['gap'][-1]:.4f}, "
        f"staleness mean {st['mean_staleness']:.2f} "
        f"(max lag {st['max_lag']:.0f} <= tau), "
        f"gate refusals {thr.history['gate_refusals'][-1]:.0f}"
    )

    # serverless: no parameter server at all. Each node keeps a W replica,
    # commits locally, and averages with graph neighbors (Metropolis
    # weights) at every round boundary; the int8 wire codec quantizes the
    # exchanged replicas with error feedback (core/wire.py). Sparse graphs
    # pay a consensus tax set by the mixing matrix's spectral gap — on a
    # ring of 8 it is 0.195 (slow), on a 2x4 torus 0.500 — so the torus
    # run below doubles the rounds to buy enough exchanges and lands
    # within reach of the parameter-server gap above.
    print("async, tau=2, gossip transport (torus topology, int8 wire)...")
    from repro.core.gossip import build_adjacency, mixing_matrix, spectral_gap

    for topo in ("ring", "torus", "complete"):
        g = spectral_gap(mixing_matrix(build_adjacency(topo, n_dev)))
        print(f"    spectral gap {topo:9s} {g:.3f}")
    gap = spectral_gap(mixing_matrix(build_adjacency("torus", n_dev)))
    gsp = DMTRLEstimator(
        engine="async",
        async_options=AsyncOptions(
            tau=2, async_delays=delays, transport="gossip",
            n_workers=n_dev, topology="torus", codec="int8",
        ),
        **dict(base, rounds=2 * base["rounds"]),
    ).fit(sp.train)
    sg = cv.staleness_summary(gsp.history)
    print(
        f"  final gap {gsp.history['gap'][-1]:.4f}, "
        f"spectral gap {gap:.3f} (consensus contraction/exchange), "
        f"{sg['n_exchanges']} edge exchanges, "
        f"edge staleness mean {sg['mean_edge_staleness']:.2f} "
        f"max {sg['max_edge_staleness']:.0f}"
    )

    if args.trace:
        n = obs.export_chrome(args.trace)
        obs.disable()
        breakdown = obs.phase_breakdown()
        top = sorted(
            breakdown.items(), key=lambda kv: -kv[1]["total_s"]
        )[:6]
        print(f"trace: {n} spans -> {os.path.abspath(args.trace)}")
        print("  top phases by inclusive wall-clock:")
        for name, row in top:
            print(
                f"    {name:16s} {row['count']:5d} x "
                f"{row['mean_s'] * 1e3:8.2f} ms = {row['total_s']:.3f} s"
            )
        print("  open chrome://tracing (or ui.perfetto.dev) and load the file")


if __name__ == "__main__":
    main()
