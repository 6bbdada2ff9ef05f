"""Local SDCA: naive == block-Gram == Pallas kernel; dual ascent property."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dual as dm
from repro.core import omega as om
from repro.core import sdca
from repro.core.dmtrl import DMTRLConfig, make_w_step_round
from repro.core.losses import get_loss, registered_losses
from repro.core.sdca import local_sdca_block, local_sdca_naive, sample_coords
from repro.data.synthetic import synthetic


@pytest.fixture(scope="module")
def data():
    return synthetic(1, m=4, d=30, n_train_avg=80, n_test_avg=20, seed=7).train


def _args(data, i, loss, key, H=96):
    coords = sample_coords(key, H, data.n[i], data.n_max)
    w = 0.05 * jax.random.normal(key, (data.d,))
    alpha = jnp.zeros((data.n_max,))
    return (
        data.x[i],
        data.y[i],
        alpha,
        w,
        data.n[i],
        jnp.float32(0.25),
        coords,
        2.0,
        1e-3,
        loss,
    )


def _dup_args(layout, n_i, loss, key, H=192, n_cap=64, d=30):
    """One task of ``n_i`` samples (3, 17 or 60 at B = 64: most blocks draw
    a coordinate several times), padded to ``n_cap`` rows or packed between
    tasks of 5 and 11 samples (offset 5), with feasible nonzero duals."""
    sizes = (n_i,) if layout == "padded" else (5, n_i, 11)
    rows = n_cap if layout == "padded" else sum(sizes)
    kx, ky, ka, kv, kc = jax.random.split(key, 5)
    x = jax.random.normal(kx, (rows, d))
    x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
    y = jnp.where(jax.random.normal(ky, (rows,)) > 0, 1.0, -1.0)
    alpha = y * jax.random.uniform(ka, (rows,), minval=0.05, maxval=0.45)
    w = 0.3 * jax.random.normal(kv, (d,))
    n = jnp.int32(n_i)
    coords = sample_coords(kc, H, n, n_cap)
    args = (x, y, alpha, w, n, jnp.float32(0.25), coords, 2.0, 1e-3, loss)
    kw = {} if layout == "padded" else {"offset": jnp.int32(5), "n_cap": n_cap}
    return args, kw


# heavy duplicates at B = 64
_DUP_CASES = [
    pytest.param((64, layout, n_i), id=f"{layout}-n{n_i}")
    for layout in ("padded", "packed")
    for n_i in (3, 17, 60)
]
# block sizes over the fixture's task 1, then the duplicate cases
_BLOCK_CASES = [pytest.param((b, None, None), id=str(b)) for b in (16, 32, 96)] + _DUP_CASES


@pytest.mark.parametrize("loss_name", sorted(registered_losses()))
@pytest.mark.parametrize("block", _BLOCK_CASES)
def test_block_equals_naive(data, loss_name, block):
    block, layout, n_i = block
    loss = get_loss(loss_name)
    key = jax.random.PRNGKey(11)
    if layout is None:
        args, kw = _args(data, 1, loss, key), {}
    else:
        args, kw = _dup_args(layout, n_i, loss, key)
        dup = np.asarray(args[6]).reshape(-1, block)
        assert all(len(set(cb)) < block for cb in dup)  # every block repeats
    da1, r1 = local_sdca_naive(*args, **kw)
    da2, r2 = local_sdca_block(*args, block=block, **kw)
    np.testing.assert_allclose(np.asarray(da1), np.asarray(da2), atol=2e-5)
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2), atol=2e-5)
    assert np.abs(np.asarray(da1)).max() > 1e-3  # the round moved


def _nested_loop_carries(jaxpr, depth=0):
    """Avals carried by every scan or while loop nested inside another."""
    out = []
    for eqn in jaxpr.eqns:
        p = eqn.params
        carry = None
        if eqn.primitive.name == "scan":
            lo = p["num_consts"]
            carry = p["jaxpr"].in_avals[lo : lo + p["num_carry"]]
        elif eqn.primitive.name == "while":
            carry = p["body_jaxpr"].in_avals[p["body_nconsts"] :]
        if carry is not None and depth >= 1:
            out += list(carry)
        for v in p.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out += _nested_loop_carries(sub, depth + (carry is not None))
    return out


def _nested_loop_primitives(jaxpr, loops=0):
    """Names of the primitives inside every scan or while loop nested in
    another (``loops`` counts the loops around ``jaxpr``)."""
    names = {eqn.primitive.name for eqn in jaxpr.eqns} if loops >= 2 else set()
    for eqn in jaxpr.eqns:
        inner = loops + (eqn.primitive.name in ("scan", "while"))
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    names |= _nested_loop_primitives(sub, inner)
    return names


@pytest.mark.parametrize("tasks", [1, 3])
@pytest.mark.parametrize("layout", ["padded", "packed"])
def test_block_recursion_carries_no_sample_axis(layout, tasks):
    """The B-step recursion carries only the block's (B,) deltas (per task):
    dalpha is gathered before it and scatter-added after it, never carried,
    so no step reads or writes a task's whole dual vector."""
    B, n_cap = 64, 200
    args, kw = _dup_args(layout, 17, get_loss("hinge"), jax.random.PRNGKey(3), n_cap=n_cap)
    solve = lambda x, y, a, w, n, s, c: local_sdca_block(
        x, y, a, w, n, s, c, *args[7:], block=B, **kw
    )
    if tasks > 1:  # vmapped over tasks, as the engines run it
        batched = tuple(jnp.stack([v] * tasks) for v in args[:7])
        solve = jax.vmap(solve)
    else:
        batched = args[:7]
    carries = _nested_loop_carries(jax.make_jaxpr(solve)(*batched).jaxpr)
    assert carries, "no loop nested in the block scan"
    assert all(a.size <= tasks * B for a in carries), [a.str_short() for a in carries]


@pytest.mark.parametrize("tasks", [1, 3])
@pytest.mark.parametrize("layout", ["padded", "packed"])
def test_block_recursion_has_no_scatter(layout, tasks):
    """Each recursion step writes its delta with a select over the block's
    lanes: a scatter there costs an in-bounds predicate at every step."""
    args, kw = _dup_args(layout, 17, get_loss("hinge"), jax.random.PRNGKey(3), n_cap=200)
    solve = lambda x, y, a, w, n, s, c: local_sdca_block(
        x, y, a, w, n, s, c, *args[7:], block=64, **kw
    )
    if tasks > 1:  # vmapped over tasks, as the engines run it
        batched = tuple(jnp.stack([v] * tasks) for v in args[:7])
        solve = jax.vmap(solve)
    else:
        batched = args[:7]
    found = _nested_loop_primitives(jax.make_jaxpr(solve)(*batched).jaxpr)
    assert "dot_general" in found, "no recursion nested in the block scan"
    assert not found & {"scatter", "scatter-add", "scatter_add"}, found


def _scatter_block_solve(G, q, xr, at0, yb, cb, kappa, loss):
    """sdca_block_solve with each step's delta written by a scatter, the
    form the select replaced: the reference for bit equality."""
    B = cb.shape[0]
    same = (cb[:, None] == cb[None, :]).astype(q.dtype)

    def body(k, deltas):
        corr = jnp.dot(G[k], deltas)
        c = q[k] + kappa * (xr[k] + corr)
        a = kappa * G[k, k]
        atilde = at0[k] + jnp.sum(same[k] * deltas)
        delta = loss.sdca_delta(atilde, c, a, yb[k])
        return deltas.at[k].set(delta)

    return jax.lax.fori_loop(0, B, body, q * 0.0)


@pytest.mark.parametrize("loss_name", sorted(registered_losses()))
@pytest.mark.parametrize("block", _DUP_CASES)
def test_select_write_equals_scatter_write(monkeypatch, loss_name, block):
    """The select writes exactly what the scatter wrote: three tasks'
    rounds, vmapped, agree bit for bit in dalpha and r."""
    block, layout, n_i = block
    loss = get_loss(loss_name)
    tasks = [_dup_args(layout, n_i, loss, jax.random.PRNGKey(s)) for s in (11, 12, 13)]
    kw = tasks[0][1]
    batched = tuple(jnp.stack(v) for v in zip(*(args[:7] for args, _ in tasks)))
    # a fresh function per call, so each traces the sdca_block_solve of its time
    rnd = lambda: jax.jit(
        jax.vmap(
            lambda x, y, a, w, n, s, c: local_sdca_block(
                x, y, a, w, n, s, c, 2.0, 1e-3, loss, block=block, **kw
            )
        )
    )(*batched)
    da_new, r_new = rnd()
    monkeypatch.setattr(sdca, "sdca_block_solve", _scatter_block_solve)
    da_old, r_old = rnd()
    assert np.abs(np.asarray(da_new)).max() > 1e-3  # the rounds moved
    np.testing.assert_array_equal(np.asarray(da_new), np.asarray(da_old))
    np.testing.assert_array_equal(np.asarray(r_new), np.asarray(r_old))


@pytest.mark.parametrize("loss_name", ["hinge", "squared", "smoothed_hinge"])
def test_kernel_backend_equals_jnp_block(data, loss_name):
    """pallas_block (per-block kernel) matches block_gram for the same key."""
    from repro.core.solver_backends import get_backend

    loss = get_loss(loss_name)
    key = jax.random.PRNGKey(13)
    i, H = 0, 64
    w = 0.05 * jax.random.normal(key, (data.d,))
    alpha = jnp.zeros((data.n_max,))
    solve_args = (data.x[i], data.y[i], alpha, w, data.n[i], jnp.float32(0.25), key)
    s1 = get_backend("block_gram").make(loss, 2.0, 1e-3, H, block=32)
    s2 = get_backend("pallas_block").make(loss, 2.0, 1e-3, H, block=32)
    da1, r1 = s1(*solve_args)
    da2, r2 = s2(*solve_args)
    np.testing.assert_allclose(np.asarray(da1), np.asarray(da2), atol=2e-5)
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2), atol=2e-5)


def test_coords_within_bounds(data):
    for i in range(data.m):
        coords = sample_coords(jax.random.PRNGKey(i), 1000, data.n[i], data.n_max)
        assert int(coords.min()) >= 0
        assert int(coords.max()) < int(data.n[i])


@pytest.mark.parametrize("loss_name", ["hinge", "squared", "logistic"])
def test_w_step_round_monotone_dual_ascent(data, loss_name):
    """Each communication round must not decrease D(alpha) (Lemma 3 with the
    safe rho guarantees ascent in expectation; with lemma-10 rho and eta=1
    the per-round ascent holds deterministically here)."""
    cfg = DMTRLConfig(
        loss=loss_name, lam=1e-3, local_iters=64, solver="block_gram", block_size=32
    )
    loss = get_loss(loss_name)
    sigma, _ = om.init_sigma(data.m)
    rho = float(om.rho_lemma10(sigma))
    round_fn = make_w_step_round(cfg, data.n_max, rho)
    alpha = jnp.zeros((data.m, data.n_max))
    W = jnp.zeros((data.m, data.d))
    prev = float(dm.dual_objective(data, alpha, sigma, cfg.lam, loss))
    key = jax.random.PRNGKey(17)
    for t in range(6):
        key, sub = jax.random.split(key)
        alpha, W = round_fn(data, alpha, W, sigma, sub)
        cur = float(dm.dual_objective(data, alpha, sigma, cfg.lam, loss))
        assert cur >= prev - 1e-4, (loss_name, t, prev, cur)
        prev = cur


def test_w_invariant_after_rounds(data):
    """Carried W must equal W(alpha) after any number of rounds."""
    cfg = DMTRLConfig(loss="hinge", lam=1e-3, local_iters=64)
    sigma, _ = om.init_sigma(data.m)
    round_fn = make_w_step_round(cfg, data.n_max, 1.0)
    alpha = jnp.zeros((data.m, data.n_max))
    W = jnp.zeros((data.m, data.d))
    key = jax.random.PRNGKey(23)
    for _ in range(3):
        key, sub = jax.random.split(key)
        alpha, W = round_fn(data, alpha, W, sigma, sub)
    W2 = dm.weights_from_alpha(data, alpha, sigma, cfg.lam)
    np.testing.assert_allclose(np.asarray(W), np.asarray(W2), atol=1e-4)
