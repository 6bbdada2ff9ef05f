"""DMTRLEstimator facade: engine-registry parity, options, warm start.

Parity anchors:
  * estimator(engine=E) must be BIT-identical to E's own function (the
    adapters only normalize signatures/returns);
  * through the facade, distributed and async(tau=0) stay bit-identical
    (the PR-1 anchor), and reference matches the mesh engines to the same
    float-op-ordering tolerance the direct APIs are tested at;
  * the 8-device mesh variant runs in a subprocess (device count must be
    set before jax initializes) and is marked slow.
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import (
    AsyncOptions,
    DMTRLConfig,
    DMTRLEstimator,
    MeshAxes,
    NotFittedError,
    available_engines,
    get_engine,
)
from repro.core.async_dmtrl import fit_async
from repro.core.distributed import fit_distributed
from repro.core.dmtrl import fit

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# engine registry
# ---------------------------------------------------------------------------
def test_engine_registry_contents():
    names = set(available_engines())
    assert {"reference", "distributed", "async"} <= names
    assert get_engine("reference").needs_mesh is False
    assert get_engine("async").options_cls is AsyncOptions
    assert get_engine("distributed").options_cls is None


def test_unknown_engine_lists_choices():
    with pytest.raises(KeyError, match="reference"):
        get_engine("banana")
    with pytest.raises(KeyError, match="banana"):
        DMTRLEstimator(engine="banana")


# ---------------------------------------------------------------------------
# facade <-> engine function bit parity
# ---------------------------------------------------------------------------
def test_reference_engine_bit_parity(small_problem, small_cfg):
    res = fit(small_cfg, small_problem.train)
    est = DMTRLEstimator(engine="reference", config=small_cfg).fit(
        small_problem.train
    )
    assert np.array_equal(est.W_, np.asarray(res.W))
    assert np.array_equal(est.alpha_, np.asarray(res.alpha))
    assert np.array_equal(est.sigma_, np.asarray(res.sigma))
    assert np.array_equal(est.omega_, np.asarray(res.omega))
    np.testing.assert_array_equal(est.history["gap"], res.history["gap"])
    assert est.rho_per_outer_ == res.rho_per_outer


def test_distributed_engine_bit_parity(small_problem, small_cfg, one_device_mesh):
    W, sigma, st, hist = fit_distributed(
        small_cfg, small_problem.train, one_device_mesh, MeshAxes(data="data")
    )
    est = DMTRLEstimator(
        engine="distributed", config=small_cfg, mesh=one_device_mesh,
        axes=MeshAxes(data="data"),
    ).fit(small_problem.train)
    assert np.array_equal(est.W_, np.asarray(W))
    assert np.array_equal(est.sigma_, np.asarray(sigma))
    assert np.array_equal(est.alpha_, np.asarray(st.alpha))
    np.testing.assert_array_equal(est.history["gap"], hist["gap"])


def test_async_engine_bit_parity(small_problem, small_cfg, one_device_mesh):
    W, sigma, st, hist = fit_async(
        small_cfg, small_problem.train, one_device_mesh, MeshAxes(data="data")
    )
    est = DMTRLEstimator(
        engine="async", config=small_cfg, mesh=one_device_mesh,
        async_options=AsyncOptions(tau=0),
    ).fit(small_problem.train)
    assert np.array_equal(est.W_, np.asarray(W))
    assert np.array_equal(est.sigma_, np.asarray(sigma))
    np.testing.assert_array_equal(est.history["w_staleness"], hist["w_staleness"])


def test_cross_engine_parity_one_device(small_problem, small_cfg, one_device_mesh):
    """Facade-level cross-engine anchor: distributed == async(tau=0) bitwise;
    reference matches both to the float-op-ordering tolerance the direct
    APIs are pinned at (test_distributed.py)."""
    ref = DMTRLEstimator(engine="reference", config=small_cfg).fit(
        small_problem.train
    )
    dist = DMTRLEstimator(
        engine="distributed", config=small_cfg, mesh=one_device_mesh
    ).fit(small_problem.train)
    asyn = DMTRLEstimator(
        engine="async", config=small_cfg, mesh=one_device_mesh,
        async_options=AsyncOptions(tau=0),
    ).fit(small_problem.train)
    assert np.array_equal(dist.W_, asyn.W_)
    assert np.array_equal(dist.alpha_, asyn.alpha_)
    assert np.array_equal(dist.sigma_, asyn.sigma_)
    np.testing.assert_allclose(ref.W_, dist.W_, atol=2e-4)
    np.testing.assert_allclose(ref.sigma_, dist.sigma_, atol=1e-5)


_PARITY_SUBPROC = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    import jax, numpy as np
    sys.path.insert(0, {repo!r} + "/src")
    from repro.core import AsyncOptions, DMTRLConfig, DMTRLEstimator, MeshAxes
    from repro.launch.mesh import make_mesh
    from repro.data.synthetic import synthetic

    sp = synthetic(1, m=8, d=32, n_train_avg=70, n_test_avg=20, seed=2)
    cfg = DMTRLConfig(loss="hinge", lam=1e-3, outer_iters=2, rounds=3,
                      local_iters=64, solver="block_gram", block_size=32, seed=0)
    mesh = make_mesh((8,), ("data",))
    ax = MeshAxes(data="data")
    ref = DMTRLEstimator(engine="reference", config=cfg).fit(sp.train)
    dist = DMTRLEstimator(engine="distributed", config=cfg, mesh=mesh,
                          axes=ax).fit(sp.train)
    asyn = DMTRLEstimator(engine="async", config=cfg, mesh=mesh, axes=ax,
                          async_options=AsyncOptions(tau=0)).fit(sp.train)
    out = dict(
        bit_dist_async=bool(np.array_equal(dist.W_, asyn.W_)
                            and np.array_equal(dist.sigma_, asyn.sigma_)),
        ref_dist_werr=float(np.max(np.abs(ref.W_ - dist.W_))),
    )
    print(json.dumps(out))
    """
)


@pytest.mark.slow
def test_cross_engine_parity_eight_devices():
    code = _PARITY_SUBPROC.format(repo=REPO)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=1200,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["bit_dist_async"] is True
    assert res["ref_dist_werr"] < 2e-4


# ---------------------------------------------------------------------------
# config split / option validation
# ---------------------------------------------------------------------------
def test_async_knobs_rejected_as_core_params():
    """The config holds the algorithm's fields alone; the async engine's
    knobs live in AsyncOptions and are unknown config fields."""
    assert [f.name for f in dataclasses.fields(DMTRLConfig)] == [
        "loss", "lam", "eta", "outer_iters", "rounds", "local_iters",
        "solver", "block_size", "rho_mode", "rho_fixed", "omega_jitter",
        "omega_regularizer", "seed", "track_every",
    ]
    knobs = [f.name for f in dataclasses.fields(AsyncOptions)]
    assert len(knobs) == 9
    for name in knobs:
        with pytest.raises(ValueError, match="unknown config fields"):
            DMTRLEstimator(engine="async", **{name: 1})
    with pytest.raises(ValueError, match="AsyncOptions"):
        DMTRLEstimator(engine="async", tau=3)


def test_unknown_config_field_rejected():
    with pytest.raises(ValueError, match="unknown config fields"):
        DMTRLEstimator(engine="reference", stepsize=0.1)


def test_reference_engine_rejects_mesh_and_options(one_device_mesh):
    with pytest.raises(ValueError, match="single-process"):
        DMTRLEstimator(engine="reference", mesh=one_device_mesh)
    with pytest.raises(ValueError, match="reference"):
        DMTRLEstimator(engine="reference", async_options=AsyncOptions())
    with pytest.raises(ValueError, match='engine="async"'):
        DMTRLEstimator(engine="distributed", async_options=AsyncOptions())


def test_async_options_eager_validation():
    for bad in ("fast", "adaptive", None, 1.5, -1):
        with pytest.raises(ValueError, match="tau"):
            AsyncOptions(tau=bad)
    with pytest.raises(ValueError, match="omega_delay"):
        AsyncOptions(omega_delay=-1)
    with pytest.raises(ValueError, match="async_delays"):
        AsyncOptions(async_delays=(1, 0))
    # valid forms
    assert AsyncOptions(tau="auto", async_delays=(1, 2)).tau == "auto"


def test_async_options_reach_the_engine(small_problem, small_cfg, one_device_mesh):
    """The estimator hands its AsyncOptions to fit_async unchanged: the
    two routes give bit-identical results."""
    W1, s1, _, _ = fit_async(
        small_cfg, small_problem.train, one_device_mesh, MeshAxes(data="data"),
        options=AsyncOptions(tau=0, omega_delay=1),
    )
    est = DMTRLEstimator(
        engine="async", config=small_cfg, mesh=one_device_mesh,
        async_options=AsyncOptions(tau=0, omega_delay=1),
    ).fit(small_problem.train)
    assert np.array_equal(est.W_, np.asarray(W1))
    assert np.array_equal(est.sigma_, np.asarray(s1))


# ---------------------------------------------------------------------------
# warm start / predict surface
# ---------------------------------------------------------------------------
def test_partial_fit_continues(small_problem, small_cfg):
    est = DMTRLEstimator(engine="reference", config=small_cfg).fit(
        small_problem.train
    )
    gap0 = est.history["gap"][-1]
    n0 = len(est.history["round"])
    alpha0 = est.alpha_.copy()
    est.partial_fit(small_problem.train)
    assert len(est.history["round"]) == 2 * n0
    # rounds continue, not restart
    assert est.history["round"][n0] == est.history["round"][n0 - 1] + 1
    assert est.history["gap"][-1] <= gap0 + 1e-6
    assert not np.array_equal(est.alpha_, alpha0)
    assert est.n_fit_calls_ == 2


def test_partial_fit_first_call_equals_fit(small_problem, small_cfg):
    a = DMTRLEstimator(engine="reference", config=small_cfg).fit(
        small_problem.train
    )
    b = DMTRLEstimator(engine="reference", config=small_cfg).partial_fit(
        small_problem.train
    )
    assert np.array_equal(a.W_, b.W_)
    assert np.array_equal(a.alpha_, b.alpha_)


def test_partial_fit_warm_start_mesh_engine(small_problem, small_cfg, one_device_mesh):
    """Warm start must round-trip through mesh padding: W(alpha) invariant."""
    from repro.core import dual as dual_mod
    import jax.numpy as jnp

    est = DMTRLEstimator(
        engine="distributed", config=small_cfg, mesh=one_device_mesh
    ).fit(small_problem.train)
    est.partial_fit(small_problem.train)
    W2 = dual_mod.weights_from_alpha(
        small_problem.train, jnp.asarray(est.alpha_), jnp.asarray(est.sigma_),
        small_cfg.lam,
    )
    np.testing.assert_allclose(est.W_, np.asarray(W2), atol=1e-4)


def test_predict_and_decision_function(small_problem, small_cfg):
    est = DMTRLEstimator(engine="reference", config=small_cfg).fit(
        small_problem.train
    )
    te = small_problem.test
    x0 = np.asarray(te.x[0, :4])
    z = est.decision_function(x0, tasks=0)
    np.testing.assert_allclose(z, x0 @ est.W_[0], atol=1e-5)
    labels = est.predict(x0, tasks=0)
    assert set(np.unique(labels)) <= {-1.0, 1.0}
    np.testing.assert_array_equal(labels, np.where(z >= 0, 1.0, -1.0))
    # per-row task ids
    t = np.array([0, 1, 2, 3])
    z2 = est.decision_function(np.asarray(te.x[:, 0]), tasks=t)
    for i in range(4):
        assert z2[i] == pytest.approx(float(np.asarray(te.x[i, 0]) @ est.W_[i]), abs=1e-5)
    # MTLData input returns the masked (m, n_max) matrix
    zm = est.decision_function(te)
    assert zm.shape == (te.m, te.n_max)
    # score is an accuracy for hinge
    assert 0.0 <= est.score(te) <= 1.0


def test_predict_validation(small_problem, small_cfg):
    est = DMTRLEstimator(engine="reference", config=small_cfg)
    with pytest.raises(NotFittedError):
        est.predict(np.zeros((2, 16)), tasks=0)
    est.fit(small_problem.train)
    with pytest.raises(ValueError, match="tasks"):
        est.decision_function(np.zeros((2, small_problem.train.d)))
    with pytest.raises(ValueError, match="task ids"):
        est.decision_function(
            np.zeros((1, small_problem.train.d)), tasks=small_problem.train.m
        )
    with pytest.raises(ValueError, match="features"):
        est.decision_function(np.zeros((2, 3)), tasks=0)
    with pytest.raises(ValueError, match="array inputs"):
        est.decision_function(small_problem.test, tasks=3)


def test_history_requires_fit(small_cfg):
    with pytest.raises(NotFittedError):
        DMTRLEstimator(engine="reference", config=small_cfg).history
