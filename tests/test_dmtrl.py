"""End-to-end DMTRL (Algorithm 1) behaviour + paper-claim spot checks."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dmtrl import DMTRLConfig, fit
from repro.core import dual as dm
from repro.core import omega as om
from repro.core.baselines import fit_centralized_mtrl, fit_ssdca, fit_stl
from repro.data.synthetic import synthetic


@pytest.fixture(scope="module")
def splits():
    return synthetic(1, m=8, d=40, n_train_avg=100, n_test_avg=60, seed=0)


@pytest.fixture(scope="module")
def fitted(splits):
    cfg = DMTRLConfig(
        loss="hinge", lam=1e-3, outer_iters=4, rounds=8, local_iters=128,
        solver="block_gram", block_size=64, seed=0,
    )
    return cfg, fit(cfg, splits.train)


def test_gap_decreases(fitted):
    _, res = fitted
    gaps = res.history["gap"]
    # within each outer iteration the gap is non-increasing up to noise
    assert gaps[-1] < gaps[0] * 0.1
    assert gaps[-1] < 0.1


def test_w_alpha_invariant(fitted, splits):
    cfg, res = fitted
    W2 = dm.weights_from_alpha(splits.train, res.alpha, res.sigma, cfg.lam)
    np.testing.assert_allclose(np.asarray(res.W), np.asarray(W2), atol=1e-4)


def test_sigma_constraints(fitted):
    _, res = fitted
    s = np.asarray(res.sigma)
    assert float(np.trace(s)) == pytest.approx(1.0, abs=1e-3)
    assert np.linalg.eigvalsh(s).min() > 0


def test_task_correlation_recovery(fitted, splits):
    """Paper Fig. 2: learned task correlations match the ground truth."""
    _, res = fitted
    learned = np.asarray(om.correlation_from_sigma(res.sigma))
    truth = splits.corr_true
    iu = np.triu_indices(truth.shape[0], k=1)
    align = np.corrcoef(learned[iu], truth[iu])[0, 1]
    assert align > 0.7, align


def test_dmtrl_beats_stl_on_correlated_tasks(splits):
    """Paper Tables 2/3 qualitative claim: exploiting task relations helps
    when tasks are related and data per task is limited."""
    small = synthetic(1, m=8, d=40, n_train_avg=40, n_test_avg=120, seed=2)
    cfg = DMTRLConfig(
        loss="hinge", lam=1e-3, outer_iters=3, rounds=6, local_iters=96, seed=0
    )
    res = fit(cfg, small.train)
    stl = fit_stl(cfg, small.train)
    err_mtl = float(dm.error_rate(small.test, jnp.asarray(res.W)))
    err_stl = float(dm.error_rate(small.test, jnp.asarray(stl.W)))
    assert err_mtl <= err_stl + 0.01, (err_mtl, err_stl)


def test_ssdca_converges_to_same_dual(splits):
    """SSDCA (single machine, exact updates) and DMTRL optimize the same
    objective; with Omega fixed both must approach the same dual value."""
    from repro.core import dual
    from repro.core.losses import get_loss

    data = synthetic(1, m=4, d=24, n_train_avg=60, n_test_avg=20, seed=3).train
    cfg = DMTRLConfig(
        loss="hinge", lam=1e-2, outer_iters=1, rounds=25, local_iters=128,
        omega_regularizer="identity_stl", seed=0,
    )
    res = fit(cfg, data)
    _, _, hist = fit_ssdca(cfg, data, passes=25)
    loss = get_loss("hinge")
    sigma, _ = om.init_sigma(data.m)
    d_dmtrl = float(dual.dual_objective(data, res.alpha, sigma, cfg.lam, loss))
    d_ssdca = hist["dual"][-1]
    assert d_dmtrl == pytest.approx(d_ssdca, rel=0.05), (d_dmtrl, d_ssdca)


def test_centralized_mtrl_parity_squared_loss():
    """Paper Table 2: DMTRL reaches the centralized MTRL solution."""
    sp = synthetic(1, m=5, d=16, n_train_avg=80, n_test_avg=40, seed=4)
    tr = sp.train
    cfg = DMTRLConfig(
        loss="squared", lam=1e-2, outer_iters=3, rounds=10, local_iters=160, seed=0
    )
    res = fit(cfg, tr)
    W_c, sigma_c, _ = fit_centralized_mtrl(cfg, tr, inner_steps=500)
    rmse_d = float(dm.rmse(sp.test, jnp.asarray(res.W)))
    rmse_c = float(dm.rmse(sp.test, jnp.asarray(W_c)))
    assert rmse_d == pytest.approx(rmse_c, rel=0.1), (rmse_d, rmse_c)


def test_rho_grows_with_learned_correlation(fitted):
    _, res = fitted
    # after Omega learning on correlated tasks rho exceeds the identity value 1
    assert res.rho_per_outer[0] == pytest.approx(1.0)
    assert res.rho_per_outer[-1] > 1.5
