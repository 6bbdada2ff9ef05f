#!/usr/bin/env python3
"""Smoke run of the main path on a TPU: the quickest proof that it starts.

    python chip_smoke.py            # one chip: training, kernel, scoring
    python chip_smoke.py --chips 4  # four chips: the mesh engine only

One chip, at the paper's full widths, with data generated from ``--seed``:

  train    MNIST shape (10 one-vs-all tasks, d=784, 12,000 samples/task)
           through ``DMTRLEstimator(engine="distributed")`` on a one-chip
           mesh (block_gram, hinge, local_iters = n_max). The duality gap
           must be finite and shrink, and W must agree with
           ``engine="reference"`` (see the tolerances below).
  kernel   School shape (139 tasks, d=28, squared loss) through the same
           estimator with ``solver="pallas_round"``: one W-step whose alpha
           must agree with block_gram's. The engine's compiled round must
           hold the Mosaic kernel (``tpu_custom_call``), so an interpreted
           kernel cannot pass.
  score    ``est.serving_scheduler(batch=64)`` answers requests over all
           tasks around one ``partial_fit`` hot-swap; every score must match
           a NumPy ``W[task] . x`` for the snapshot version it records.

Four chips (``--chips 4``): the distributed engine on a 4-device ``data``
mesh at the MNIST shape (10 tasks padded to 12, three per worker) against
the reference engine on one of those chips, plus a check that x, alpha and
W are sharded over 4 distinct devices.

Each phase prints one JSON line with its device, its seconds split into
compile (JAX's trace, lowering and backend compile events) and the rest,
and its agreement error. These are smoke timings, not benchmark figures.
The last line is ``{"ok": true, "device": {...}}``. Without a TPU the
script exits non-zero before running anything; a failed phase raises.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# Agreement tolerances, as max |A - B| / max |B|.
# Engines compared at the same (highest) matmul precision differ only in
# summation order: float32 rounding, amplified a little by the recursion.
TOL_SAME_PRECISION = 1e-4
# The distributed engine at the TPU's default precision (one bf16 pass per
# float32 matmul, relative rounding 2^-8 per product) against the
# highest-precision reference: a sanity bound on bf16 drift, not parity.
TOL_DEFAULT_PRECISION = 1e-1
# A served score against NumPy in float64, as |z - z_ref| / (|w| |x|):
# the scorer's dot runs at default precision (bf16 rounding, 2^-8).
TOL_SCORE = 1e-2

COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class CompileClock:
    """Running total of the compile seconds JAX reports."""

    def __init__(self):
        self.total = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.total += duration


def timed(clock: CompileClock, fn):
    """(result, compile_s, run_s) of ``fn()``; run_s is wall minus compile."""
    c0, t0 = clock.total, time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    compile_s = clock.total - c0
    return out, compile_s, wall - compile_s


def rel_err(a, b) -> float:
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def check_gap(gap, outer_iters: int, rounds: int, what: str) -> None:
    """The duality gap is finite and shrinks over every W-step. (Each
    Omega-step changes Sigma and with it the dual problem, so the gap may
    rise from one W-step to the next.)"""
    import numpy as np

    gap = np.asarray(gap).reshape(outer_iters, rounds)
    check(bool(np.all(np.isfinite(gap))), f"{what}: non-finite gap {gap}")
    check(bool(np.all(gap[:, -1] < gap[:, 0])), f"{what}: gap did not shrink {gap}")


def report(phase: str, device: str, **fields) -> None:
    print(json.dumps({"phase": phase, "device": device, **fields}), flush=True)


def mnist_config(n_max: int) -> dict:
    return dict(loss="hinge", outer_iters=2, rounds=3, local_iters=n_max)


def train_phase(jax, mesh, splits, clock, device):
    from repro.core import DMTRLEstimator

    train = splits.train
    cfg = mnist_config(train.n_max)
    est, c_s, r_s = timed(
        clock,
        lambda: DMTRLEstimator(engine="distributed", mesh=mesh, **cfg).fit(train),
    )
    gap = est.history_["gap"]
    check_gap(gap, cfg["outer_iters"], cfg["rounds"], "distributed")
    with jax.default_matmul_precision("highest"):
        ref, ref_c, ref_r = timed(
            clock, lambda: DMTRLEstimator(engine="reference", **cfg).fit(train)
        )
        hi = DMTRLEstimator(engine="distributed", mesh=mesh, **cfg).fit(train)
    err_hi = rel_err(hi.W_, ref.W_)
    err_default = rel_err(est.W_, ref.W_)
    check(err_hi <= TOL_SAME_PRECISION, f"W vs reference at highest: {err_hi}")
    check(err_default <= TOL_DEFAULT_PRECISION, f"W vs reference: {err_default}")
    report(
        "train", device, shape=[train.m, train.n_max, train.d],
        compile_s=c_s, run_s=r_s, reference_compile_s=ref_c,
        reference_run_s=ref_r, gap_first=float(gap[0]), gap_last=float(gap[-1]),
        w_err_highest=err_hi, tol_highest=TOL_SAME_PRECISION,
        w_err_default_precision=err_default, tol_default=TOL_DEFAULT_PRECISION,
    )
    return est


def kernel_phase(jax, mesh, seed, clock, device):
    from repro.core import DMTRLEstimator, MeshAxes
    from repro.core.distributed import (
        init_state,
        make_distributed_round,
        shard_mtl_data,
    )
    from repro.data.synthetic import school_like

    train = school_like(seed=seed).train
    # one W-step, compared on alpha: the Omega-step after it takes the square
    # root of W W^T, whose 139 - 28 null eigenvalues turn float32 rounding
    # in W into ~1e-3 differences in Sigma (measured on the CPU), so W after
    # an Omega-step measures that amplification, not the kernel
    cfg = dict(loss="squared", outer_iters=1, rounds=3)
    with jax.default_matmul_precision("highest"):
        kern, c_s, r_s = timed(
            clock,
            lambda: DMTRLEstimator(
                engine="distributed", mesh=mesh, solver="pallas_round", **cfg
            ).fit(train),
        )
        jnp_est = DMTRLEstimator(
            engine="distributed", mesh=mesh, solver="block_gram", **cfg
        ).fit(train)
    check_gap(kern.history_["gap"], cfg["outer_iters"], cfg["rounds"], "pallas_round")
    err = rel_err(kern.alpha_, jnp_est.alpha_)
    check(err <= TOL_SAME_PRECISION, f"pallas_round vs block_gram alpha: {err}")
    # the engine's own round program, compiled for this chip
    axes = MeshAxes()
    data, m, d = shard_mtl_data(train, mesh, axes)
    st = init_state(data, mesh, axes, m, d)
    round_fn = make_distributed_round(
        kern.config, mesh, axes, m, data.n_max, d, rho=1.0
    )
    hlo = round_fn.lower(
        data.x, data.y, data.mask, data.n, st.alpha, st.W, st.sigma,
        jax.random.PRNGKey(0),
    ).compile().as_text()
    check("tpu_custom_call" in hlo, "no compiled Mosaic kernel in the round")
    report(
        "kernel", device, shape=[train.m, train.n_max, train.d],
        compile_s=c_s, run_s=r_s, tpu_custom_call=True,
        alpha_err_vs_block_gram=err, tol=TOL_SAME_PRECISION,
    )


def score_phase(est, splits, clock, device, n_requests=320):
    import numpy as np

    from repro.serve.mtl import ScoreRequest

    test = splits.test
    x = np.asarray(test.x)
    n = np.asarray(test.n)
    sched = est.serving_scheduler(batch=64)
    weights = {sched.version: np.asarray(sched.snapshot.W, np.float64)}

    def submit(offset):
        reqs = []
        for i in range(offset, offset + n_requests):
            t = i % test.m
            reqs.append(ScoreRequest(task=t, x=x[t, (i // test.m) % n[t]]))
        for r in reqs:
            sched.submit(r)
        return reqs

    def serve():
        first = submit(0)
        sched.step()
        sched.step()  # two tiles on the first snapshot
        est.partial_fit(splits.train)  # hot-swap: pushes the new W
        weights[sched.version] = np.asarray(sched.snapshot.W, np.float64)
        sched.run_until_idle()  # the queued rest runs on the new W
        second = submit(n_requests)
        sched.run_until_idle()
        return first + second

    reqs, c_s, r_s = timed(clock, serve)
    check(len(weights) == 2, f"partial_fit did not swap the model: {weights.keys()}")
    errs, used = [], set()
    for r in reqs:
        check(r.status == "done", f"request not served: {r.status}")
        check(r.snapshot_version in weights, f"unknown version {r.snapshot_version}")
        used.add(r.snapshot_version)
        w = weights[r.snapshot_version][r.task]
        xr = np.asarray(r.x, np.float64)
        scale = max(np.linalg.norm(w) * np.linalg.norm(xr), 1e-30)
        errs.append(abs(r.score - float(w @ xr)) / scale)
    check(used == set(weights), f"versions served {used}, published {set(weights)}")
    err = max(errs)
    check(err <= TOL_SCORE, f"served score vs NumPy: {err}")
    report(
        "score", device, requests=len(reqs), tasks=test.m,
        versions=sorted(used), compile_s=c_s, run_s=r_s,
        score_err=err, tol=TOL_SCORE,
    )


def mesh4_phase(jax, devices, splits, clock, device):
    from repro.core import DMTRLEstimator, MeshAxes
    from repro.core.distributed import init_state, shard_mtl_data
    from repro.launch.mesh import make_mesh

    train = splits.train
    mesh = make_mesh((4,), ("data",), devices=devices)
    cfg = mnist_config(train.n_max)
    with jax.default_matmul_precision("highest"):
        ref = DMTRLEstimator(engine="reference", **cfg).fit(train)
        est, c_s, r_s = timed(
            clock,
            lambda: DMTRLEstimator(engine="distributed", mesh=mesh, **cfg).fit(train),
        )
    err = rel_err(est.W_, ref.W_)
    check(err <= TOL_SAME_PRECISION, f"4-chip W vs reference: {err}")
    axes = MeshAxes()
    data, m, d = shard_mtl_data(train, mesh, axes)
    st = init_state(data, mesh, axes, m, d)
    check(m == 12, f"10 tasks should pad to 12 on 4 workers, got {m}")
    placement = {}
    for name, arr in (("x", data.x), ("alpha", st.alpha), ("W", st.W)):
        shards = arr.addressable_shards
        devs = {s.device.id for s in shards}
        rows = sorted({s.data.shape[0] for s in shards})
        check(len(devs) == 4, f"{name} sits on devices {devs}")
        check(rows == [m // 4], f"{name} shards hold {rows} task rows")
        placement[name] = sorted(devs)
    report(
        "mesh4", device, shape=[train.m, train.n_max, train.d], tasks_padded=m,
        compile_s=c_s, run_s=r_s, w_err_vs_reference=err,
        tol=TOL_SAME_PRECISION, shard_devices=placement,
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
            "nothing was run",
            file=sys.stderr,
        )
        return 1
    if len(devices) < args.chips:
        print(
            f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
            f"JAX found {len(devices)}",
            file=sys.stderr,
        )
        return 1
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.data.synthetic import mnist_like
        from repro.launch.mesh import make_mesh
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside it: {e}", file=sys.stderr)
        return 1

    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    device = f"{dev.device_kind} x{args.chips}"
    splits = mnist_like(scale=1.0, seed=args.seed)
    if args.chips == 4:
        mesh4_phase(jax, devices[:4], splits, clock, device)
    else:
        mesh = make_mesh((1,), ("data",), devices=devices[:1])
        est = train_phase(jax, mesh, splits, clock, device)
        kernel_phase(jax, mesh, args.seed, clock, device)
        score_phase(est, splits, clock, device)
    result = {
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(devices),
        },
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
