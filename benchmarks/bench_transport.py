"""Transport x tau sweep + gossip/codec wire grid on the straggler workload.

Two grids, one row list:

1. For every ``core.transport`` member and staleness bound the bench runs
   the same heterogeneous-worker fit (one straggler, ``--straggler``x
   slower) and records the protocol-level health metrics the transports
   account through the shared CommitReceipt path: commits/sec, mean/max
   staleness, gate refusals.
2. The wire grid (``core/wire.py`` x ``core/gossip.py``): threaded and
   gossip (complete + ring) under every codec (``none``/``bf16``/``int8``),
   recording the bytes actually shipped (``wire_stats``), the payload
   reduction vs the raw f32 wire, the measured final-objective convergence
   gap against that transport's own exact (codec="none") run, and the
   topology's spectral gap. ``check()`` asserts the PR's claims: payload
   strictly decreases none > bf16 > int8, int8 beats 4x on the server
   wire (alpha elision — see DESIGN.md §13), the convergence gap stays
   bounded, and gossip-complete matches threaded within 1e-5.

Results land in BENCH_transport.json at the repo root.

    PYTHONPATH=src python -m benchmarks.bench_transport
    PYTHONPATH=src python -m benchmarks.bench_transport --tiny
    PYTHONPATH=src python -m benchmarks.bench_transport --workers 4 --tau 0 1 2
    PYTHONPATH=src python -m benchmarks.bench_transport --no-multiprocess
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _problem(n_workers: int, tiny: bool):
    from repro.data.synthetic import synthetic

    if tiny:
        return synthetic(1, m=n_workers, d=16, n_train_avg=40, n_test_avg=10,
                         seed=2)
    return synthetic(1, m=n_workers, d=32, n_train_avg=80, n_test_avg=20,
                     seed=2)


def _config(tiny: bool, seed: int = 0):
    from repro.core import DMTRLConfig

    return DMTRLConfig(
        loss="hinge", lam=1e-4,
        outer_iters=2, rounds=3 if tiny else 8,
        local_iters=32 if tiny else 64,
        solver="block_gram", block_size=32, seed=seed,
        track_every=10**6,  # one objective sample at the end of each W-step
    )


def run_one(transport: str, tau, n_workers: int, straggler: int,
            tiny: bool = False, seed: int = 0):
    from repro.core import AsyncOptions, MeshAxes
    from repro.core import convergence as cv
    from repro.core.async_dmtrl import fit_async
    from repro.launch.mesh import make_mesh

    sp = _problem(n_workers, tiny)
    delays = (1,) * (n_workers - 1) + (straggler,)
    cfg = _config(tiny, seed)
    opts = AsyncOptions(
        tau=tau,
        async_delays=delays,
        transport=transport,
        n_workers=None if transport == "simulated" else n_workers,
    )
    mesh = (
        make_mesh((n_workers,), ("data",))
        if transport == "simulated"
        else None
    )
    t0 = time.perf_counter()
    _, _, _, hist = fit_async(cfg, sp.train, mesh, MeshAxes(data="data"), options=opts)
    wall = time.perf_counter() - t0
    s = cv.staleness_summary(hist)
    commits = int(len(hist["tau_trace"]))
    return {
        "transport": transport,
        "tau": tau,
        "workers": n_workers,
        "straggler": straggler,
        "commit_events": commits,
        "contributions": s["n_commits"],
        "wall_s": wall,
        "commits_per_sec": commits / wall,
        "mean_staleness": s["mean_staleness"],
        "max_staleness": s["max_staleness"],
        "max_lag": s["max_lag"],
        "gate_refusals": int(hist["gate_refusals"][-1]) if commits else 0,
        "tau_final": int(hist["tau_trace"][-1]) if commits else 0,
        "final_gap": float(hist["gap"][-1]) if len(hist["gap"]) else None,
    }


def run_codec_one(transport: str, topology, codec: str, n_workers: int,
                  tiny: bool = False, seed: int = 0):
    """One wire-grid cell: drive the transport manually so ``wire_stats``
    (bytes shipped / raw) is readable before close()."""
    import jax
    import numpy as np

    from repro.core import AsyncOptions, MeshAxes
    from repro.core import omega_regularizers as omega_reg
    from repro.core.dmtrl import _rho_value
    from repro.core.transport import get_transport

    sp = _problem(n_workers, tiny)
    opts = AsyncOptions(
        tau=0, transport=transport, n_workers=n_workers,
        topology=topology, codec=codec,
    )
    cfg = _config(tiny, seed)
    reg = omega_reg.resolve_regularizer(cfg, None, m=sp.train.m)
    t = get_transport(transport).factory()
    t.setup(cfg, sp.train, mesh=None, axes=MeshAxes(), reg=reg,
            init=None, track=True, options=opts)
    t0 = time.perf_counter()
    try:
        key = jax.random.PRNGKey(cfg.seed)
        rho_sigma = t.rho_sigma()
        for p in range(cfg.outer_iters):
            rho = _rho_value(cfg, rho_sigma, n_blocks_scale=1.0, reg=reg)
            key, ok = jax.random.split(key)
            t.run_w_step(p, rho, ok)
            if reg.learns:
                sig_t, om_t = reg.step(t.w_true(), cfg.omega_jitter)
                sig, om = t.pad_sigma(sig_t, om_t)
                t.install_sigma(sig, om, defer=False)
                rho_sigma = sig
        W, _, _, hist = t.result()
        s = dict(t.wire_stats)
    finally:
        t.close()
    wall = time.perf_counter() - t0
    shipped = s["snapshot_bytes"] + s["commit_bytes"] + s["mix_bytes"]
    raw = (
        s["raw_snapshot_bytes"] + s["raw_commit_bytes"] + s["raw_mix_bytes"]
    )
    return {
        "transport": transport,
        "topology": (topology if isinstance(topology, str) else "explicit"),
        "codec": codec,
        "tau": 0,
        "workers": n_workers,
        "wall_s": wall,
        "payload_nbytes": int(shipped),
        "raw_payload_nbytes": int(raw),
        "payload_reduction": (raw / shipped) if shipped else None,
        "snapshot_bytes": int(s["snapshot_bytes"]),
        "commit_bytes": int(s["commit_bytes"]),
        "mix_bytes": int(s["mix_bytes"]),
        "spectral_gap": s.get("spectral_gap"),
        "final_objective": float(np.asarray(hist["primal"])[-1]),
        "final_gap": float(hist["gap"][-1]) if len(hist["gap"]) else None,
        "W_norm": float(np.linalg.norm(np.asarray(W))),
    }


# thresholds of the measured claims (check() + the CI bench-smoke step)
CODEC_GAP_BOUND = {"none": 1e-5, "bf16": 5e-3, "int8": 2e-2}
INT8_SERVER_REDUCTION = 4.0  # alpha elision pushes the server wire past 4x
INT8_GOSSIP_REDUCTION = 3.0  # mix wire ships full replicas (no alpha leg)
PARITY_OBJECTIVE_TOL = 1e-5  # gossip complete == threaded acceptance bar


def check(rows) -> None:
    """Claim assertions over the wire grid (CI bench-smoke step)."""
    grid = [r for r in rows if "codec" in r]
    assert grid, "no codec rows in the sweep"
    by = {(r["transport"], r["topology"], r["codec"]): r for r in grid}
    members = sorted({(r["transport"], r["topology"]) for r in grid})
    for tr, topo in members:
        none = by[(tr, topo, "none")]
        bf16 = by[(tr, topo, "bf16")]
        int8 = by[(tr, topo, "int8")]
        # payload strictly decreases under the lossy codecs
        assert (
            none["payload_nbytes"]
            > bf16["payload_nbytes"]
            > int8["payload_nbytes"]
        ), (tr, topo)
        assert none["payload_reduction"] == 1.0, (tr, topo)
        # measured reduction floors: the server wire (alpha elision)
        # clears 4x under int8; the gossip mix wire ships full replicas
        # so its aggregate floor is lower (DESIGN.md §13)
        floor = (
            INT8_GOSSIP_REDUCTION if tr == "gossip"
            else INT8_SERVER_REDUCTION
        )
        assert int8["payload_reduction"] >= floor, (
            tr, topo, int8["payload_reduction"],
        )
        # bounded convergence gap vs the member's own exact run
        ref = abs(none["final_objective"])
        for r in (bf16, int8):
            gap = abs(r["final_objective"] - none["final_objective"])
            assert gap <= CODEC_GAP_BOUND[r["codec"]] * max(1.0, ref), (
                tr, topo, r["codec"], gap,
            )
    # gossip on a complete graph matches the threaded server (exact wire)
    if ("threaded", "complete") in members and (
        "gossip", "complete",
    ) in members:
        obj_t = by[("threaded", "complete", "none")]["final_objective"]
        obj_g = by[("gossip", "complete", "none")]["final_objective"]
        assert abs(obj_g - obj_t) <= PARITY_OBJECTIVE_TOL * max(
            1.0, abs(obj_t)
        ), (obj_g, obj_t)
        assert by[("gossip", "complete", "none")]["spectral_gap"] >= 0.999


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--tau", nargs="+", default=[0, 1, 4, "auto"])
    ap.add_argument("--straggler", type=int, default=4)
    ap.add_argument(
        "--tiny", action="store_true",
        help="small fixture + short schedule (CI bench-smoke)",
    )
    ap.add_argument("--out", default=None)
    ap.add_argument(
        "--no-multiprocess", action="store_true",
        help="skip the multiprocess member (process spawns pay a jax "
        "import each)",
    )
    args = ap.parse_args()
    taus = [t if t == "auto" else int(t) for t in args.tau]
    if args.tiny:
        taus = [0, "auto"]

    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={args.workers}"
    )
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

    transports = ["simulated", "threaded", "gossip"]
    if not (args.no_multiprocess or args.tiny):
        transports.append("multiprocess")

    rows = []
    print(
        "transport,tau,commit_events,commits_per_sec,mean_staleness,"
        "gate_refusals,final_gap"
    )
    for transport in transports:
        for tau in taus:
            r = run_one(transport, tau, args.workers, args.straggler,
                        tiny=args.tiny)
            rows.append(r)
            print(
                f"{r['transport']},{r['tau']},{r['commit_events']},"
                f"{r['commits_per_sec']:.2f},{r['mean_staleness']:.3f},"
                f"{r['gate_refusals']},{r['final_gap']:.5f}",
                flush=True,
            )

    print(
        "transport,topology,codec,payload_nbytes,payload_reduction,"
        "spectral_gap,final_objective"
    )
    for transport, topology in (
        ("threaded", "complete"),
        ("gossip", "complete"),
        ("gossip", "ring"),
    ):
        for codec in ("none", "bf16", "int8"):
            r = run_codec_one(transport, topology, codec, args.workers,
                              tiny=args.tiny)
            rows.append(r)
            sg = r["spectral_gap"]
            print(
                f"{r['transport']},{r['topology']},{r['codec']},"
                f"{r['payload_nbytes']},{r['payload_reduction']:.2f},"
                f"{'-' if sg is None else f'{sg:.3f}'},"
                f"{r['final_objective']:.6f}",
                flush=True,
            )
    check(rows)
    print("check() passed")
    out = args.out or os.path.join(
        os.path.dirname(__file__), "..", "BENCH_transport.json"
    )
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"wrote {os.path.abspath(out)}")


if __name__ == "__main__":
    main()
