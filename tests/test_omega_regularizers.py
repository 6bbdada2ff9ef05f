"""Omega-regularizer family (core/omega_regularizers.py).

Every registered member must produce a symmetric PD Sigma with a finite
rho bound through every engine; the named members additionally pin their
defining constraints (trace-1, fixed graph coupling, shrinkage toward
identity, STL equivalence).
"""
import dataclasses

import numpy as np
import pytest

from repro.core import (
    DMTRLEstimator,
    available_regularizers,
    get_regularizer,
)
from repro.core.dmtrl import fit
from repro.core.omega_regularizers import resolve_regularizer


def _fit_with(small_problem, small_cfg, reg_name, **params):
    est = DMTRLEstimator(
        engine="reference", config=small_cfg,
        regularizer=reg_name, regularizer_params=params or None,
    )
    return est.fit(small_problem.train)


def test_registry_has_the_family():
    names = set(available_regularizers())
    assert {"trace_constraint", "graph_laplacian", "identity_stl",
            "frobenius_shrunk"} <= names


def test_unknown_regularizer_lists_choices():
    with pytest.raises(KeyError, match="trace_constraint"):
        get_regularizer("banana")


@pytest.mark.parametrize("name", sorted(
    {"trace_constraint", "identity_stl", "frobenius_shrunk"}
))
def test_member_sigma_pd_and_rho_finite(small_problem, small_cfg, name):
    est = _fit_with(small_problem, small_cfg, name)
    s = np.asarray(est.sigma_)
    assert np.allclose(s, s.T, atol=1e-6)
    assert np.linalg.eigvalsh(s).min() > 0
    assert np.trace(s) == pytest.approx(1.0, abs=1e-3)
    assert all(np.isfinite(r) and r > 0 for r in est.rho_per_outer_)


def test_graph_laplacian_fixed_sigma(small_problem, small_cfg):
    m = small_problem.train.m
    A = np.zeros((m, m))
    for i in range(m - 1):  # chain graph
        A[i, i + 1] = A[i + 1, i] = 1.0
    est = _fit_with(small_problem, small_cfg, "graph_laplacian", adjacency=A)
    s = np.asarray(est.sigma_)
    # Sigma never updates: it equals the trace-normalized (L + eps I)^{-1}
    L = np.diag(A.sum(1)) - A
    sigma0 = np.linalg.inv(L + 1e-3 * np.eye(m))
    sigma0 /= np.trace(sigma0)
    np.testing.assert_allclose(s, sigma0, atol=1e-5)
    assert np.linalg.eigvalsh(s).min() > 0
    assert all(np.isfinite(r) and r > 0 for r in est.rho_per_outer_)
    # coupled tasks: neighbours on the chain have positive covariance
    assert s[0, 1] > 0


def test_graph_laplacian_validation(small_cfg):
    with pytest.raises(ValueError, match="exactly one"):
        get_regularizer("graph_laplacian")
    with pytest.raises(ValueError, match="symmetric"):
        get_regularizer("graph_laplacian",
                        adjacency=np.array([[0.0, 1.0], [0.0, 0.0]]))
    with pytest.raises(ValueError, match="non-negative"):
        get_regularizer("graph_laplacian",
                        adjacency=np.array([[0.0, -1.0], [-1.0, 0.0]]))
    reg = get_regularizer("graph_laplacian", adjacency=np.zeros((3, 3)))
    with pytest.raises(ValueError, match="3 tasks"):
        reg.init(5)


def test_identity_stl_keeps_sigma_fixed_and_matches_stl(small_problem, small_cfg):
    """identity_stl holds Sigma at I/m through every Omega-step and fits
    bit for bit what the STL baseline fits."""
    from repro.core.baselines import fit_stl

    stl = fit_stl(small_cfg, small_problem.train)
    est = _fit_with(small_problem, small_cfg, "identity_stl")
    assert np.array_equal(est.W_, np.asarray(stl.W))
    assert np.array_equal(est.alpha_, np.asarray(stl.alpha))
    assert np.array_equal(est.sigma_, np.asarray(stl.sigma))
    m = small_problem.train.m
    np.testing.assert_allclose(est.sigma_, np.eye(m) / m, atol=1e-7)


def test_trace_constraint_is_the_default_bitwise(small_problem, small_cfg):
    legacy = fit(small_cfg, small_problem.train)
    est = DMTRLEstimator(engine="reference", config=small_cfg).fit(
        small_problem.train
    )
    assert est.regularizer.name == "trace_constraint"
    assert np.array_equal(est.W_, np.asarray(legacy.W))
    assert np.array_equal(est.sigma_, np.asarray(legacy.sigma))


def test_frobenius_shrunk_interpolates(small_problem, small_cfg):
    zy = _fit_with(small_problem, small_cfg, "trace_constraint")
    sh = _fit_with(small_problem, small_cfg, "frobenius_shrunk", shrinkage=0.5)
    m = small_problem.train.m
    eye = np.eye(m) / m

    def offdiag_mass(s):
        return float(np.abs(s - np.diag(np.diag(s))).sum())

    # shrunk couplings sit strictly between the ZY solution and identity
    assert offdiag_mass(sh.sigma_) < offdiag_mass(zy.sigma_)
    assert offdiag_mass(sh.sigma_) > 0
    # shrinkage=1 collapses the update to I/m exactly
    full = _fit_with(small_problem, small_cfg, "frobenius_shrunk", shrinkage=1.0)
    np.testing.assert_allclose(full.sigma_, eye, atol=1e-6)
    with pytest.raises(ValueError, match="shrinkage"):
        get_regularizer("frobenius_shrunk", shrinkage=1.5)


def test_resolve_regularizer_precedence(small_cfg):
    assert resolve_regularizer(small_cfg).name == "trace_constraint"
    stl_cfg = dataclasses.replace(small_cfg, omega_regularizer="identity_stl")
    assert resolve_regularizer(stl_cfg).name == "identity_stl"
    assert resolve_regularizer(small_cfg, "identity_stl").name == "identity_stl"
    # an explicit member wins over the config's name
    reg = resolve_regularizer(stl_cfg, get_regularizer("trace_constraint"))
    assert reg.name == "trace_constraint"


def test_family_through_mesh_engines(small_problem, small_cfg, one_device_mesh):
    """A fixed-graph member must run identically through distributed and
    async(tau=0) — the family is engine-agnostic."""
    m = small_problem.train.m
    A = np.ones((m, m)) - np.eye(m)
    kw = dict(
        config=small_cfg, regularizer="graph_laplacian",
        regularizer_params={"adjacency": A}, mesh=one_device_mesh,
    )
    dist = DMTRLEstimator(engine="distributed", **kw).fit(small_problem.train)
    asyn = DMTRLEstimator(engine="async", **kw).fit(small_problem.train)
    assert np.array_equal(dist.W_, asyn.W_)
    assert np.array_equal(dist.sigma_, asyn.sigma_)
    ref = DMTRLEstimator(
        engine="reference", config=small_cfg, regularizer="graph_laplacian",
        regularizer_params={"adjacency": A},
    ).fit(small_problem.train)
    np.testing.assert_allclose(ref.W_, dist.W_, atol=2e-4)
