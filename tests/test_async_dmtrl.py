"""Bounded-staleness async engine (core/async_dmtrl.py).

Anchors:
  * tau=0 must be BIT-identical to fit_distributed — the sync path and the
    async tick share the same factored local-solve/server-reduce pieces, so
    any refactor drift shows up here first. 1-device runs in-process; the
    8-device mesh runs in a subprocess (device count must be set before jax
    initializes) and is marked slow.
  * tau in {1, 4} under a deterministic straggler schedule must still
    converge (gap within 2x of the synchronous gap for the same number of
    per-worker rounds).
  * stale snapshot reads must never mix coordinates across tasks.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import convergence as cv
from repro.core.async_dmtrl import AsyncOptions, fit_async
from repro.core.distributed import MeshAxes, fit_distributed
from repro.core.dmtrl import DMTRLConfig
from repro.data.synthetic import synthetic

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tau0_async(small_problem, small_cfg, one_device_mesh):
    return fit_async(
        small_cfg, small_problem.train, one_device_mesh, MeshAxes(data="data")
    )


def test_tau0_bit_parity_one_device(
    small_problem, small_cfg, one_device_mesh, tau0_async
):
    W1, s1, st1, h1 = fit_distributed(
        small_cfg, small_problem.train, one_device_mesh, MeshAxes(data="data")
    )
    W2, s2, st2, h2 = tau0_async
    assert np.array_equal(W1, W2), np.max(np.abs(W1 - W2))
    assert np.array_equal(s1, s2)
    assert np.array_equal(np.asarray(st1.alpha), np.asarray(st2.alpha))
    # the anchor also pins the bookkeeping: no staleness at tau=0
    assert h2["w_staleness"].max() == 0
    assert h2["w_lag"].max() == 0


def test_tau0_homogeneous_clock_matches_round_count(small_cfg, tau0_async):
    _, _, _, hist = tau0_async
    total = small_cfg.outer_iters * small_cfg.rounds
    assert len(hist["gap"]) == total
    # homogeneous delay-1 workers: one commit per tick, clock == round count
    np.testing.assert_array_equal(hist["tick"], np.arange(1, total + 1))


_STRAGGLER_SUBPROC = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, sys
    import jax, numpy as np
    sys.path.insert(0, {repo!r} + "/src")
    from repro.core.async_dmtrl import AsyncOptions, fit_async
    from repro.core.distributed import MeshAxes, fit_distributed
    from repro.core.dmtrl import DMTRLConfig
    from repro.launch.mesh import make_mesh
    from repro.data.synthetic import synthetic

    sp = synthetic(1, m=4, d=16, n_train_avg=40, n_test_avg=10, seed=3)
    base = dict(loss="hinge", lam=1e-3, outer_iters=1, rounds=4,
                local_iters=32, solver="block_gram", block_size=32, seed=0)
    mesh = make_mesh((4,), ("data",))
    ax = MeshAxes(data="data")
    _, _, _, h_sync = fit_distributed(DMTRLConfig(**base), sp.train, mesh, ax)
    out = dict(sync_gap=float(h_sync["gap"][-1]))
    mask = np.asarray(sp.train.mask)
    for tau in (1, 4):
        opts = AsyncOptions(tau=tau, async_delays=(1, 1, 1, 3))
        _, _, st, h = fit_async(DMTRLConfig(**base), sp.train, mesh, ax,
                                options=opts)
        out[f"tau{{tau}}_gap"] = float(h["gap"][-1])
        out[f"tau{{tau}}_stal"] = int(h["w_staleness"].max())
        out[f"tau{{tau}}_lag"] = int(h["w_lag"].max())
        # stale-snapshot reads must never mix coordinates across tasks:
        # padded coords stay exactly zero, every real task's block moves
        alpha = np.asarray(st.alpha)[: sp.train.m]
        out[f"tau{{tau}}_pad_leak"] = bool(np.any(alpha[mask == 0.0] != 0.0))
        out[f"tau{{tau}}_all_tasks_moved"] = bool(
            all(np.any(alpha[i][mask[i] == 1.0] != 0.0)
                for i in range(sp.train.m))
        )
    cfg_auto = DMTRLConfig(**dict(base, outer_iters=2))
    opts_auto = AsyncOptions(tau="auto", async_delays=(1, 1, 1, 3))
    _, _, _, h_auto = fit_async(cfg_auto, sp.train, mesh, ax,
                                options=opts_auto)
    out["auto_gap"] = float(h_auto["gap"][-1])
    out["auto_tau_max"] = int(h_auto["tau_trace"].max())
    out["auto_tau_start"] = int(h_auto["tau_trace"][0])
    print(json.dumps(out))
    """
)


@pytest.mark.slow
def test_straggler_converges_within_2x_sync_gap():
    """Deterministic 3x straggler on a 4-worker mesh, tau in {1, 4}: the
    async gap after the same per-worker round budget stays within 2x of
    sync, and the schedule really produced stale commits."""
    code = _STRAGGLER_SUBPROC.format(repo=REPO)
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=600,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
    )
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    for tau in (1, 4):
        assert r[f"tau{tau}_gap"] <= 2.0 * abs(r["sync_gap"]) + 1e-9, r
        assert r[f"tau{tau}_stal"] >= 1, r
        # genuinely-stale snapshot reads never mixed task coordinates
        assert not r[f"tau{tau}_pad_leak"], r
        assert r[f"tau{tau}_all_tasks_moved"], r
    # a larger staleness bound must actually allow more lag
    assert r["tau4_lag"] >= r["tau1_lag"], r
    # tau="auto": starts bulk-synchronous, the straggler's gate refusals
    # must widen the bound, and the run still converges within 2x of sync
    assert r["auto_tau_start"] == 0, r
    assert r["auto_tau_max"] >= 1, r
    assert r["auto_gap"] <= 2.0 * abs(r["sync_gap"]) + 1e-9, r


def test_stale_snapshots_never_mix_tasks(one_device_mesh):
    """Property: per-task dual blocks only move where that task has real
    samples. On a 1-device mesh (G=1) snapshots are always fresh, so this
    covers the padding invariance of the engine plumbing; the genuinely-
    stale multi-worker case is asserted inside the straggler subprocess
    test above (pad_leak / all_tasks_moved outputs)."""
    sp = synthetic(1, m=4, d=12, n_train_avg=24, n_test_avg=6, seed=5)
    data = sp.train
    for tau in (0, 2):
        cfg = DMTRLConfig(
            loss="squared", lam=1e-3, outer_iters=1, rounds=5, local_iters=32,
            solver="block_gram", block_size=32, seed=7,
        )
        _, _, state, _ = fit_async(
            cfg, data, one_device_mesh, MeshAxes(data="data"),
            options=AsyncOptions(tau=tau),
        )
        alpha = np.asarray(state.alpha)[: data.m]
        mask = np.asarray(data.mask)
        # padded coordinates (mask==0) must be exactly zero: SDCA only draws
        # indices in [0, n_i) so cross-task/padding leakage would land here
        assert np.all(alpha[mask == 0.0] == 0.0)
        # each real task must have moved its own block
        for i in range(data.m):
            assert np.any(alpha[i][mask[i] == 1.0] != 0.0)


def test_adapt_tau_controller():
    """tau="auto" decision rule: widen on gate refusals, narrow on unused
    slack, clamp to [0, tau_max]."""
    from repro.core.async_dmtrl import _adapt_tau

    slack = {"max_lag": 0.0}
    tight = {"max_lag": 3.0}
    # gate refused starts -> widen (regardless of the window summary)
    assert _adapt_tau(0, 2, slack, 8) == 1
    assert _adapt_tau(3, 1, tight, 8) == 4
    # cap
    assert _adapt_tau(8, 5, slack, 8) == 8
    # no refusals and lag strictly under the bound -> narrow
    assert _adapt_tau(3, 0, slack, 8) == 2
    # floor
    assert _adapt_tau(0, 0, slack, 8) == 0
    # no refusals but the slack was fully used -> hold
    assert _adapt_tau(3, 0, tight, 8) == 3


def test_tau_auto_one_device_matches_sync(
    small_problem, small_cfg, one_device_mesh
):
    """A single worker can never be gated, so tau="auto" must stay at 0 and
    reproduce the synchronous engine bit-exactly."""
    W1, s1, st1, _ = fit_distributed(
        small_cfg, small_problem.train, one_device_mesh, MeshAxes(data="data")
    )
    W2, s2, st2, h2 = fit_async(
        small_cfg, small_problem.train, one_device_mesh, MeshAxes(data="data"),
        options=AsyncOptions(tau="auto"),
    )
    assert np.array_equal(W1, W2)
    assert np.array_equal(np.asarray(st1.alpha), np.asarray(st2.alpha))
    assert h2["tau_trace"].max() == 0


def test_omega_overlap_converges(small_problem, one_device_mesh):
    """omega_delay > 0: the Sigma install lands mid-W-step; the run must
    still reduce the duality gap and end with a valid trace-1 Sigma."""
    cfg = DMTRLConfig(
        loss="hinge", lam=1e-3, outer_iters=3, rounds=4, local_iters=32,
        solver="block_gram", block_size=32, seed=0,
    )
    W, sigma, _, hist = fit_async(
        cfg, small_problem.train, one_device_mesh, MeshAxes(data="data"),
        options=AsyncOptions(tau=1, omega_delay=2),
    )
    assert np.trace(sigma) == pytest.approx(1.0, abs=1e-4)
    assert hist["gap"][-1] < hist["gap"][0]


def test_staleness_summary_and_effective_curve(small_cfg, tau0_async):
    _, _, _, hist = tau0_async
    s = cv.staleness_summary(hist)
    assert s["n_commits"] == small_cfg.outer_iters * small_cfg.rounds
    assert s["max_staleness"] == 0.0
    ticks, gaps = cv.effective_gap_curve(hist)
    assert ticks.shape == gaps.shape
    assert cv.ticks_to_gap(ticks, gaps, target=gaps[-1]) <= ticks[-1]


def test_omega_delay_exceeding_round_budget_still_installs(
    small_problem, one_device_mesh
):
    """omega_delay larger than a W-step's commit count: the pending Sigma
    must land at the next barrier, never be silently dropped."""
    cfg = DMTRLConfig(
        loss="hinge", lam=1e-3, outer_iters=2, rounds=3, local_iters=32,
        solver="block_gram", block_size=32, seed=0,
    )
    _, sigma, _, _ = fit_async(
        cfg, small_problem.train, one_device_mesh, MeshAxes(data="data"),
        options=AsyncOptions(omega_delay=50),
    )
    m = small_problem.train.m
    # still learned: not the I/m init the run started from
    assert not np.allclose(sigma, np.eye(m) / m, atol=1e-3)
    assert np.trace(sigma) == pytest.approx(1.0, abs=1e-4)


def test_bad_config_rejected(small_problem, small_cfg, one_device_mesh):
    # only ints >= 0 and "auto" are staleness bounds
    for bad in (-1, "adaptive", None, 1.5):
        with pytest.raises(ValueError, match="tau"):
            AsyncOptions(tau=bad)
    with pytest.raises(ValueError, match="omega_delay"):
        AsyncOptions(omega_delay=-2)
    # the delay schedule's length is checked against the mesh's workers at
    # setup; an empty tuple must hit that check, not fall back to all-ones
    for delays in ((1, 2), ()):
        with pytest.raises(ValueError, match="async_delays"):
            fit_async(
                small_cfg, small_problem.train, one_device_mesh,
                MeshAxes(data="data"), options=AsyncOptions(async_delays=delays),
            )


_SUBPROC = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    import jax, numpy as np
    sys.path.insert(0, {repo!r} + "/src")
    from repro.core.async_dmtrl import AsyncOptions, fit_async
    from repro.core.distributed import MeshAxes, fit_distributed
    from repro.core.dmtrl import DMTRLConfig
    from repro.launch.mesh import make_mesh
    from repro.data.synthetic import synthetic

    sp = synthetic(1, m=8, d=24, n_train_avg=50, n_test_avg=10, seed=2)
    base = dict(loss="hinge", lam=1e-3, outer_iters=2, rounds=4,
                local_iters=32, solver="block_gram", block_size=32, seed=0)
    mesh = make_mesh((8,), ("data",))
    ax = MeshAxes(data="data")
    cfg = DMTRLConfig(**base)
    W1, s1, st1, h1 = fit_distributed(cfg, sp.train, mesh, ax)
    W2, s2, st2, h2 = fit_async(cfg, sp.train, mesh, ax)
    out = dict(
        w_bit_equal=bool(np.array_equal(W1, W2)),
        alpha_bit_equal=bool(np.array_equal(np.asarray(st1.alpha),
                                            np.asarray(st2.alpha))),
        sync_gap=float(h1["gap"][-1]),
    )
    opts4 = AsyncOptions(tau=4, async_delays=(1, 1, 1, 1, 1, 1, 1, 3))
    W4, s4, st4, h4 = fit_async(cfg, sp.train, mesh, ax, options=opts4)
    out["tau4_gap"] = float(h4["gap"][-1])
    out["tau4_max_staleness"] = int(h4["w_staleness"].max())
    print(json.dumps(out))
    """
)


@pytest.mark.slow
def test_eight_device_parity_and_straggler_convergence():
    """Acceptance anchor on a real 8-device CPU mesh: bit parity at tau=0
    and gap <= 2x sync in the same per-worker round budget at tau=4."""
    code = _SUBPROC.format(repo=REPO)
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=900,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
    )
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["w_bit_equal"], r
    assert r["alpha_bit_equal"], r
    assert r["tau4_gap"] <= 2.0 * abs(r["sync_gap"]) + 1e-9, r
    assert r["tau4_max_staleness"] >= 1, r  # the straggler really was stale
