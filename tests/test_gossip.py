"""Decentralized gossip transport (core/gossip.py).

Anchors:
  * topology builders: ring/torus/complete/explicit adjacency are
    symmetric, zero-diagonal, connected; disconnected graphs fail at
    setup; the Metropolis mixing matrix is doubly stochastic with the
    complete graph degenerating to exactly uniform 1/G weights.
  * parity: gossip on a complete graph matches the threaded server member
    to float-association tolerance at tau=0 (the replica-mean invariant),
    and its final objective is within 1e-5 — the acceptance anchor.
  * ring topology still converges on the synthetic fixture (bounded gap
    vs the server trajectory, finite objective).
  * codec sweep none/bf16/int8: final-objective gap bounds + wire stats
    shrink monotonically.
  * random connected topologies (seeded sweep + optional hypothesis
    fuzz): mixing stays doubly stochastic, the fit stays finite and near
    the server member.
  * per-edge staleness events land in the history and
    convergence.staleness_summary picks them up.
"""
import numpy as np
import pytest

from repro.core import AsyncOptions, MeshAxes
from repro.core import convergence as cv
from repro.core.async_dmtrl import fit_async
from repro.core.gossip import build_adjacency, mixing_matrix, spectral_gap
from repro.core.transport import available_transports, get_transport

ATOL = 5e-5  # float-association tolerance (matches test_transport.py)


def _fit(cfg, data, transport, n_workers, **opt_kw):
    opts = AsyncOptions(transport=transport, n_workers=n_workers, **opt_kw)
    return fit_async(cfg, data, None, MeshAxes(data="data"), options=opts)


def _final_objective(hist):
    return float(np.asarray(hist["primal"])[-1])


def _random_connected(G, rng):
    """Random spanning tree + random extra edges: connected by build."""
    adj = np.zeros((G, G), np.int64)
    order = rng.permutation(G)
    for i in range(1, G):
        j = order[rng.integers(0, i)]
        adj[order[i], j] = adj[j, order[i]] = 1
    for _ in range(int(rng.integers(0, G))):
        a, b = rng.integers(0, G, size=2)
        if a != b:
            adj[a, b] = adj[b, a] = 1
    return adj


# ---------------------------------------------------------------------------
# topology builders
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("topology", ["ring", "torus", "complete"])
@pytest.mark.parametrize("G", [1, 2, 3, 4, 6, 8])
def test_adjacency_properties(topology, G):
    adj = build_adjacency(topology, G)
    assert adj.shape == (G, G)
    assert np.array_equal(adj, adj.T)
    assert np.all(np.diag(adj) == 0)
    assert np.all((adj == 0) | (adj == 1))


def test_ring_degrees():
    adj = build_adjacency("ring", 6)
    assert np.all(adj.sum(axis=1) == 2)


def test_torus_is_a_grid():
    adj = build_adjacency("torus", 6)  # 2 x 3 wrap-around grid
    # every node touches its 4 wrapped grid neighbors; on a 2-row torus
    # the up/down wraps coincide, leaving degree 3
    assert np.all(adj.sum(axis=1) == 3)


def test_torus_prime_degenerates_to_ring():
    np.testing.assert_array_equal(
        build_adjacency("torus", 5), build_adjacency("ring", 5)
    )


def test_explicit_adjacency_roundtrips():
    want = build_adjacency("ring", 4)
    got = build_adjacency(want, 4)
    np.testing.assert_array_equal(got, want)


def test_explicit_adjacency_validation():
    bad = np.zeros((3, 3), np.int64)
    bad[0, 1] = 1  # not symmetric
    with pytest.raises(ValueError, match="symmetric"):
        build_adjacency(bad, 3)
    with pytest.raises(ValueError, match="0/1"):
        build_adjacency(np.full((2, 2), 2.0) - 2 * np.eye(2), 2)
    eye = np.eye(3, dtype=np.int64)
    with pytest.raises(ValueError, match="zero diagonal"):
        build_adjacency(eye, 3)
    with pytest.raises(ValueError, match=r"\(4, 4\)"):
        build_adjacency(np.zeros((3, 3), np.int64), 4)
    with pytest.raises(ValueError, match="disconnected"):
        build_adjacency(np.zeros((3, 3), np.int64), 3)
    two_islands = np.zeros((4, 4), np.int64)
    two_islands[0, 1] = two_islands[1, 0] = 1
    two_islands[2, 3] = two_islands[3, 2] = 1
    with pytest.raises(ValueError, match="disconnected"):
        build_adjacency(two_islands, 4)
    with pytest.raises(ValueError, match="unknown gossip topology"):
        build_adjacency("hypercube", 4)


@pytest.mark.parametrize("topology", ["ring", "torus", "complete"])
@pytest.mark.parametrize("G", [2, 3, 4, 6, 8])
def test_mixing_matrix_doubly_stochastic(topology, G):
    M = mixing_matrix(build_adjacency(topology, G))
    np.testing.assert_allclose(M.sum(axis=0), 1.0, atol=1e-12)
    np.testing.assert_allclose(M.sum(axis=1), 1.0, atol=1e-12)
    np.testing.assert_allclose(M, M.T, atol=1e-12)
    assert np.all(M >= -1e-12)


def test_complete_graph_mixing_is_uniform():
    G = 5
    M = mixing_matrix(build_adjacency("complete", G))
    # off-diagonal weights are exactly 1/G; the diagonal takes the slack
    # 1 - (G-1)/G, one float rounding away from 1/G
    off = ~np.eye(G, dtype=bool)
    np.testing.assert_array_equal(M[off], 1.0 / G)
    np.testing.assert_allclose(M, np.full((G, G), 1.0 / G), atol=1e-15)


def test_spectral_gap_ordering():
    # denser graphs contract consensus faster
    gaps = {
        t: spectral_gap(mixing_matrix(build_adjacency(t, 8)))
        for t in ("ring", "torus", "complete")
    }
    assert gaps["complete"] == pytest.approx(1.0)
    assert gaps["ring"] < gaps["torus"] < gaps["complete"]
    # longer rings mix slower
    ring16 = spectral_gap(mixing_matrix(build_adjacency("ring", 16)))
    assert ring16 < gaps["ring"]


def test_random_connected_topologies_mix(seed_range=range(6)):
    for seed in seed_range:
        rng = np.random.default_rng(seed)
        G = int(rng.integers(2, 9))
        adj = _random_connected(G, rng)
        adj2 = build_adjacency(adj, G)  # validates
        M = mixing_matrix(adj2)
        np.testing.assert_allclose(M.sum(axis=0), 1.0, atol=1e-12)
        np.testing.assert_allclose(M.sum(axis=1), 1.0, atol=1e-12)
        assert 0.0 < spectral_gap(M) <= 1.0 + 1e-12


def test_hypothesis_random_topologies():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(st.integers(min_value=2, max_value=10), st.integers(0, 2 ** 31))
    def run(G, seed):
        rng = np.random.default_rng(seed)
        adj = build_adjacency(_random_connected(G, rng), G)
        M = mixing_matrix(adj)
        np.testing.assert_allclose(M.sum(axis=0), 1.0, atol=1e-12)
        np.testing.assert_allclose(M.sum(axis=1), 1.0, atol=1e-12)
        gap = spectral_gap(M)
        assert 0.0 < gap <= 1.0 + 1e-12

    run()


# ---------------------------------------------------------------------------
# registry / option plumbing
# ---------------------------------------------------------------------------
def test_gossip_registered():
    assert "gossip" in available_transports()
    spec = get_transport("gossip")
    assert spec.needs_mesh is False


def test_topology_and_codec_options_validated():
    with pytest.raises(ValueError, match="topology"):
        AsyncOptions(topology="hypercube")
    with pytest.raises(ValueError, match="codec"):
        AsyncOptions(codec="zstd")
    with pytest.raises(ValueError, match="topology"):
        AsyncOptions(topology=7)
    # valid spellings construct eagerly
    AsyncOptions(transport="gossip", topology="ring", codec="int8")


def test_topology_rejected_on_star_transports(small_problem, small_cfg):
    with pytest.raises(ValueError, match="gossip"):
        _fit(
            small_cfg, small_problem.train, "threaded", 2, topology="ring"
        )


def test_codec_rejected_on_simulated(small_problem, small_cfg, one_device_mesh):
    opts = AsyncOptions(transport="simulated", codec="bf16")
    with pytest.raises(ValueError, match="codec"):
        fit_async(
            small_cfg,
            small_problem.train,
            one_device_mesh,
            MeshAxes(data="data"),
            options=opts,
        )


def test_disconnected_explicit_topology_fails_at_setup(
    small_problem, small_cfg
):
    adj = tuple(
        tuple(int(v) for v in row) for row in np.zeros((2, 2), np.int64)
    )
    with pytest.raises(ValueError, match="disconnected"):
        _fit(small_cfg, small_problem.train, "gossip", 2, topology=adj)


# ---------------------------------------------------------------------------
# parity — the acceptance anchor
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def threaded_result(small_problem, small_cfg):
    return _fit(small_cfg, small_problem.train, "threaded", 4, tau=0)


def test_complete_graph_matches_threaded(
    small_problem, small_cfg, threaded_result
):
    Wt, sigt, _, ht = threaded_result
    Wg, sigg, _, hg = _fit(
        small_cfg, small_problem.train, "gossip", 4, tau=0,
        topology="complete",
    )
    np.testing.assert_allclose(Wg, Wt, atol=ATOL)
    np.testing.assert_allclose(sigg, sigt, atol=ATOL)
    # acceptance criterion: final objective within 1e-5
    assert abs(_final_objective(hg) - _final_objective(ht)) <= 1e-5


def test_ring_converges_near_server(
    small_problem, small_cfg, threaded_result
):
    _, _, _, ht = threaded_result
    Wg, _, _, hg = _fit(
        small_cfg, small_problem.train, "gossip", 4, tau=0, topology="ring"
    )
    obj_g, obj_t = _final_objective(hg), _final_objective(ht)
    assert np.isfinite(obj_g)
    assert np.all(np.isfinite(np.asarray(Wg)))
    # sparse mixing perturbs the trajectory but must stay in the same
    # basin on the tiny fixture (loose relative bound, not parity)
    assert abs(obj_g - obj_t) <= 0.2 * abs(obj_t)


@pytest.mark.parametrize("codec", ["none", "bf16", "int8"])
def test_codec_sweep_objective_gap(
    small_problem, small_cfg, threaded_result, codec
):
    _, _, _, ht = threaded_result
    _, _, _, hg = _fit(
        small_cfg, small_problem.train, "gossip", 4, tau=0, codec=codec
    )
    gap = abs(_final_objective(hg) - _final_objective(ht))
    # lossy codecs (with error feedback) stay within a small bounded gap
    # of the exact run; exact codec matches to float association
    bound = {"none": 1e-5, "bf16": 5e-3, "int8": 2e-2}[codec]
    assert gap <= bound * max(1.0, abs(_final_objective(ht)))


def test_random_topology_fit_stays_finite(small_problem, small_cfg):
    rng = np.random.default_rng(3)
    adj = build_adjacency(_random_connected(4, rng), 4)
    topo = tuple(tuple(int(v) for v in row) for row in adj)
    W, sigma, _, hist = _fit(
        small_cfg, small_problem.train, "gossip", 4, tau=0, topology=topo
    )
    assert np.all(np.isfinite(np.asarray(W)))
    assert np.isfinite(_final_objective(hist))


# ---------------------------------------------------------------------------
# per-edge staleness accounting
# ---------------------------------------------------------------------------
def test_per_edge_staleness_history_and_summary(small_problem, small_cfg):
    _, _, _, hist = _fit(
        small_cfg, small_problem.train, "gossip", 4, tau=1, topology="ring"
    )
    for k in ("e_src", "e_dst", "e_stal", "e_tick"):
        assert k in hist and len(hist[k])
    # ring on 4 nodes has 4 edges, one record per edge per exchange
    assert len(hist["e_stal"]) % 4 == 0
    summ = cv.staleness_summary(hist)
    assert summ["n_exchanges"] == len(hist["e_stal"])
    assert summ["max_edge_staleness"] >= summ["mean_edge_staleness"] >= 0.0
    assert set(summ["per_edge_mean"]) == {(0, 1), (0, 3), (1, 2), (2, 3)}


def test_server_histories_have_no_edge_keys(threaded_result):
    _, _, _, ht = threaded_result
    summ = cv.staleness_summary(ht)
    assert "n_exchanges" not in summ
    assert "e_stal" not in ht


# ---------------------------------------------------------------------------
# wire stats
# ---------------------------------------------------------------------------
def test_gossip_wire_stats_monotone_under_codecs(small_problem, small_cfg):
    totals = {}
    for codec in ("none", "bf16", "int8"):
        opts = AsyncOptions(
            transport="gossip", n_workers=4, tau=0, codec=codec
        )
        cfg = small_cfg
        from repro.core import omega_regularizers as omega_reg
        from repro.core.dmtrl import _rho_value
        import jax

        reg = omega_reg.resolve_regularizer(
            cfg, None, m=small_problem.train.m
        )
        t = get_transport("gossip").factory()
        t.setup(
            cfg, small_problem.train, mesh=None, axes=MeshAxes(),
            reg=reg, init=None, track=False, options=opts,
        )
        try:
            key = jax.random.PRNGKey(0)
            rho_sigma = t.rho_sigma()
            for p in range(cfg.outer_iters):
                rho = _rho_value(
                    cfg, rho_sigma, n_blocks_scale=1.0, reg=reg
                )
                key, ok = jax.random.split(key)
                t.run_w_step(p, rho, ok)
                if reg.learns:
                    sig_t, om_t = reg.step(t.w_true(), cfg.omega_jitter)
                    sig, om = t.pad_sigma(sig_t, om_t)
                    t.install_sigma(sig, om, defer=False)
                    rho_sigma = sig
            s = t.wire_stats
            assert s["n_exchanges"] > 0
            assert s["spectral_gap"] == pytest.approx(1.0)  # complete
            totals[codec] = (
                s["snapshot_bytes"] + s["commit_bytes"] + s["mix_bytes"]
            )
            raw = (
                s["raw_snapshot_bytes"]
                + s["raw_commit_bytes"]
                + s["raw_mix_bytes"]
            )
            assert raw == totals["none"] if codec == "none" else raw > 0
        finally:
            t.close()
    assert totals["none"] > totals["bf16"] > totals["int8"]
