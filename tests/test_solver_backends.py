"""Solver-backend registry: cross-backend iterate parity + registry API.

All backends share the key->coords derivation of ``sdca.sample_coords``, so
for one (key, shape, loss) triple every backend walks the SAME sampled
coordinate order and must produce the same iterate sequence:

  * naive / pallas_block vs block_gram: equal up to float-op reordering.
  * pallas_round vs block_gram: equal to float32 rounding (``ROUND_ATOL``).
    The fused kernel runs the block-Gram recursion step for step, but it
    reads lane k of a vector as a masked lane sum and reduces
    ``G[k, :] . deltas`` across lanes, the layout the TPU compiler accepts,
    so its sums associate differently from XLA's dot and the last bits of
    an iterate can differ (measured: <= 3e-8 on these problems).

hypothesis is an optional test dependency (see pyproject's [test] extra);
the property sweep imports it via ``pytest.importorskip`` at call time so a
missing install skips just that test instead of erroring collection.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.losses import get_loss
from repro.core.solver_backends import (
    available_backends,
    get_backend,
)

KERNEL_LOSSES = ("hinge", "squared", "smoothed_hinge")
BACKENDS = ("naive", "block_gram", "pallas_block", "pallas_round")
ROUND_ATOL = 1e-6  # float32 rounding over <= 96 coordinate steps of O(1) values


def _problem(seed, n, d, n_valid):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (n, d))
    y = jnp.sign(jax.random.normal(ks[1], (n,)))
    y = jnp.where(y == 0, 1.0, y)
    alpha = 0.1 * jax.random.normal(ks[2], (n,))
    w = 0.05 * jax.random.normal(ks[3], (d,))
    return x, y, alpha, w, jnp.int32(n_valid), jnp.float32(0.25), ks[0]


def _run_all(loss_name, seed, n, d, n_valid, H, block, backends=BACKENDS):
    loss = get_loss(loss_name)
    args = _problem(seed, n, d, n_valid)
    out = {}
    for name in backends:
        be = get_backend(name)
        solve = be.make(loss, 2.0, 1e-3, be.round_local_iters(H, block), block=block)
        da, r = solve(*args)
        out[name] = (np.asarray(da), np.asarray(r))
    return out


@pytest.mark.parametrize("loss_name", KERNEL_LOSSES)
@pytest.mark.parametrize("n,d,H,block", [(70, 33, 96, 32), (40, 17, 64, 16)])
def test_all_backends_same_iterates(loss_name, n, d, H, block):
    out = _run_all(loss_name, seed=n * d, n=n, d=d, n_valid=n - 5, H=H, block=block)
    da0, r0 = out["block_gram"]
    for name in ("naive", "pallas_block"):
        np.testing.assert_allclose(out[name][0], da0, atol=2e-5, err_msg=name)
        np.testing.assert_allclose(out[name][1], r0, atol=2e-5, err_msg=name)
    # the fused round kernel replays block_gram to float32 rounding
    np.testing.assert_allclose(out["pallas_round"][0], da0, rtol=0, atol=ROUND_ATOL)
    np.testing.assert_allclose(out["pallas_round"][1], r0, rtol=0, atol=ROUND_ATOL)


@pytest.mark.parametrize("loss_name", ["logistic", "eps_insensitive"])
def test_kernel_fallback_losses_still_parity(loss_name):
    """Losses without a closed-form kernel delta have no Pallas path: the
    Pallas backends refuse them when built instead of running a jnp
    fallback, and the jnp backends still agree on them."""
    loss = get_loss(loss_name)
    for name in ("pallas_block", "pallas_round"):
        with pytest.raises(ValueError, match="no kernel delta"):
            get_backend(name).make(loss, 2.0, 1e-3, 64, block=32)
    out = _run_all(
        loss_name, seed=3, n=48, d=20, n_valid=48, H=64, block=32,
        backends=("naive", "block_gram"),
    )
    np.testing.assert_allclose(out["naive"][0], out["block_gram"][0], atol=2e-5)
    np.testing.assert_allclose(out["naive"][1], out["block_gram"][1], atol=2e-5)


def test_pallas_round_refuses_task_block_over_vmem_budget():
    """A task block that cannot stay in VMEM (MNIST width: 12000 x 784) is
    refused with its numbers when traced; nothing else runs in its place."""
    be = get_backend("pallas_round")
    solve = be.make(get_loss("hinge"), 2.0, 1e-3, be.round_local_iters(12000, 64))
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    args = (
        f32(12000, 784), f32(12000), f32(12000), f32(784),
        jax.ShapeDtypeStruct((), jnp.int32), f32(), jax.random.PRNGKey(0),
    )
    with pytest.raises(ValueError, match=r"n_max=12000 x d=784 .* budget"):
        jax.eval_shape(solve, *args)


def test_backend_parity_property():
    """hypothesis sweep: random shapes x all three kernel losses agree."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=15, deadline=None)
    @given(
        loss_name=st.sampled_from(KERNEL_LOSSES),
        n=st.integers(20, 90),
        d=st.integers(5, 40),
        nb=st.integers(1, 3),
        block=st.sampled_from([8, 16, 32]),
        pad=st.integers(0, 10),
        seed=st.integers(0, 2**16),
    )
    def check(loss_name, n, d, nb, block, pad, seed):
        n_valid = max(n - pad, 1)
        out = _run_all(
            loss_name, seed=seed, n=n, d=d, n_valid=n_valid, H=nb * block,
            block=block,
        )
        da0, r0 = out["block_gram"]
        for name in ("naive", "pallas_block"):
            np.testing.assert_allclose(out[name][0], da0, atol=5e-5)
            np.testing.assert_allclose(out[name][1], r0, atol=5e-5)
        np.testing.assert_allclose(
            out["pallas_round"][0], da0, rtol=0, atol=ROUND_ATOL
        )
        np.testing.assert_allclose(
            out["pallas_round"][1], r0, rtol=0, atol=ROUND_ATOL
        )

    check()


def test_registry_api():
    have = available_backends()
    assert set(BACKENDS) <= set(have)
    with pytest.raises(KeyError, match="unknown solver backend"):
        get_backend("nope")
    # pallas launch accounting: the fused kernel is ONE call per round
    assert get_backend("pallas_round").pallas_calls_per_round(256, 64) == 1
    assert get_backend("pallas_block").pallas_calls_per_round(256, 64) == 4
    assert get_backend("block_gram").pallas_calls_per_round(256, 64) == 0
    assert get_backend("naive").pallas_calls_per_round(256, 64) == 0
    # H alignment contract
    assert get_backend("block_gram").round_local_iters(100, 64) == 128
    assert get_backend("naive").round_local_iters(100, 64) == 100


def test_pallas_backends_reject_sharded_features():
    loss = get_loss("hinge")
    for name in ("pallas_block", "pallas_round"):
        with pytest.raises(ValueError, match="sharded feature"):
            get_backend(name).make(loss, 2.0, 1e-3, 64, block=32, axis_name="model")


def test_mesh_engines_run_pallas_backends(one_device_mesh):
    """fit_distributed and fit_async must trace pallas backends under
    shard_map (replication checking has no pallas_call rule — the round
    builder must route through distributed.round_shard_map) and keep the
    tau=0 bit-parity anchor."""
    from repro.core.async_dmtrl import fit_async
    from repro.core.distributed import MeshAxes, fit_distributed
    from repro.core.dmtrl import DMTRLConfig
    from repro.data.synthetic import synthetic

    data = synthetic(1, m=3, d=12, n_train_avg=24, n_test_avg=6, seed=11).train
    ax = MeshAxes(data="data")
    for name in ("pallas_block", "pallas_round"):
        cfg = DMTRLConfig(
            loss="hinge", lam=1e-3, outer_iters=1, rounds=2, local_iters=16,
            solver=name, block_size=16, seed=0,
        )
        W1, _, st1, h1 = fit_distributed(cfg, data, one_device_mesh, ax)
        W2, _, st2, _ = fit_async(cfg, data, one_device_mesh, ax)
        assert np.array_equal(W1, W2), name
        assert np.array_equal(np.asarray(st1.alpha), np.asarray(st2.alpha)), name
        assert h1["gap"][-1] < h1["gap"][0], name


def test_engine_fit_runs_on_every_backend():
    """The whole Algorithm-1 driver works with each registered backend.

    (pallas_round vs block_gram is asserted per task above; across a full
    fit the runs agree to float tolerance.)"""
    from repro.core.dmtrl import DMTRLConfig, fit
    from repro.data.synthetic import synthetic

    data = synthetic(1, m=3, d=12, n_train_avg=24, n_test_avg=6, seed=11).train
    results = {}
    for name in BACKENDS:
        cfg = DMTRLConfig(
            loss="hinge", lam=1e-3, outer_iters=1, rounds=2, local_iters=16,
            solver=name, block_size=16, seed=0,
        )
        results[name] = np.asarray(fit(cfg, data, track=False).W)
    for name in ("naive", "pallas_block", "pallas_round"):
        np.testing.assert_allclose(
            results[name], results["block_gram"], atol=1e-4, err_msg=name
        )
