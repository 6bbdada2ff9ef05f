"""Packed task storage (core/mtl_data.py:PackedMTLData) == the padded layout.

Six tasks of 8 to 300 samples at d = 64: the packed rounds read the same
rows at the same coordinates as the padded ones, a packed fit agrees with
the reference engine, and tasks dealt unevenly to four workers give the
one-worker fit. The four-device case runs in a subprocess, since the
device count is fixed when JAX starts.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DMTRLEstimator, from_task_list, pack_tasks
from repro.core.distributed import (
    MeshAxes,
    init_state,
    make_distributed_round,
    shard_mtl_data,
)
from repro.core.losses import get_loss
from repro.core.mtl_data import worker_layout
from repro.core.solver_backends import get_backend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZES = (8, 300, 41, 117, 19, 64)
D = 64


def _tasks(seed=0, sizes=SIZES, d=D):
    rng = np.random.RandomState(seed)
    w = rng.randn(len(sizes), d).astype(np.float32)
    xs, ys = [], []
    for i, k in enumerate(sizes):
        x = rng.randn(k, d).astype(np.float32)
        x /= np.linalg.norm(x, axis=1, keepdims=True)
        xs.append(x)
        ys.append(np.where(x @ w[i] + 0.3 * rng.randn(k) > 0, 1.0, -1.0).astype(np.float32))
    return xs, ys


def _pack_alpha(alpha, sizes=SIZES):
    """Padded (m, n_max) dual variables in packed row order."""
    return np.concatenate([np.asarray(alpha)[i, :k] for i, k in enumerate(sizes)])


@pytest.fixture(scope="module")
def both():
    xs, ys = _tasks()
    return from_task_list(xs, ys), pack_tasks(xs, ys)


@pytest.mark.parametrize("solver", ["naive", "block_gram"])
def test_packed_round_gives_the_padded_dalpha_and_r(both, solver):
    pad, pk = both
    m, rho, lam, H, block = pad.m, 1.7, 1e-3, 320, 32
    rng = np.random.RandomState(1)
    alpha_pad = jnp.asarray(rng.uniform(0, 0.5, pad.y.shape) * pad.y * pad.mask, jnp.float32)
    W = jnp.asarray(rng.randn(m, D) * 0.1, jnp.float32)
    sig = jnp.asarray(rng.uniform(0.1, 0.3, m), jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(5), m)
    backend = get_backend(solver)
    loss = get_loss("hinge")
    dal_pad, r_pad = jax.vmap(backend.make(loss, rho, lam, H, block=block))(
        pad.x, pad.y, alpha_pad, W, pad.n, sig, keys
    )
    offsets = jnp.cumsum(pk.n) - pk.n
    dal_pk, r_pk = jax.vmap(
        backend.make(loss, rho, lam, H, block=block, n_cap=pk.n_max),
        in_axes=(None, None, None, 0, 0, 0, 0, 0),
    )(pk.x, pk.y, jnp.asarray(_pack_alpha(alpha_pad)), W, pk.n, sig, keys, offsets)
    np.testing.assert_allclose(np.asarray(dal_pk), np.asarray(dal_pad), atol=1e-6)
    np.testing.assert_allclose(np.asarray(r_pk), np.asarray(r_pad), atol=1e-6)
    assert np.abs(np.asarray(dal_pad)).max() > 1e-3  # the round moved


@pytest.mark.parametrize("solver", ["naive", "block_gram"])
def test_packed_distributed_round_equals_padded(both, one_device_mesh, solver):
    """The whole round program, rows scattered back into packed order."""
    from repro.core import DMTRLConfig

    pad, pk = both
    cfg = DMTRLConfig(solver=solver, block_size=32, lam=1e-3)
    out = []
    for raw, packed in ((pad, False), (pk, True)):
        data, m, d = shard_mtl_data(raw, one_device_mesh, MeshAxes())
        st = init_state(data, one_device_mesh, MeshAxes(), m, d)
        rf = make_distributed_round(
            cfg, one_device_mesh, MeshAxes(), m, data.n_max, d, 2.0, packed=packed
        )
        key = jax.random.PRNGKey(7)
        alpha, W = rf(data.x, data.y, data.mask, data.n, st.alpha, st.W, st.sigma, key)
        alpha, W = rf(data.x, data.y, data.mask, data.n, alpha, W, st.sigma, key)
        out.append((np.asarray(alpha), np.asarray(W)))
    (a_pad, w_pad), (a_pk, w_pk) = out
    np.testing.assert_allclose(a_pk, _pack_alpha(a_pad), atol=1e-6)
    np.testing.assert_allclose(w_pk, w_pad, atol=1e-5)


def test_packed_fit_agrees_with_reference_engine(both, one_device_mesh):
    """naive backend, float32 at the highest matmul precision: W, alpha and
    every round's duality gap."""
    pad, pk = both
    kw = dict(loss="hinge", lam=1e-3, outer_iters=2, rounds=3, solver="naive", seed=4)
    with jax.default_matmul_precision("highest"):
        ref = DMTRLEstimator(engine="reference", **kw).fit(pad)
        est = DMTRLEstimator(engine="distributed", mesh=one_device_mesh, **kw).fit(pk)
    np.testing.assert_allclose(est.W_, ref.W_, atol=5e-5)
    np.testing.assert_allclose(est.alpha_, _pack_alpha(ref.alpha_), atol=1e-5)
    gap, gap_ref = est.history_["gap"], ref.history_["gap"]
    assert gap.shape == gap_ref.shape == (6,)
    np.testing.assert_allclose(gap, gap_ref, atol=1e-5)
    assert gap[-1] < gap[0]


def test_packed_partial_fit_continues_as_padded(both, one_device_mesh):
    """A warm start from packed alpha continues the padded run's iterates."""
    pad, pk = both
    kw = dict(loss="hinge", lam=1e-3, outer_iters=1, rounds=2, block_size=32, seed=2)
    a = DMTRLEstimator(engine="distributed", mesh=one_device_mesh, **kw)
    b = DMTRLEstimator(engine="distributed", mesh=one_device_mesh, **kw)
    for _ in range(2):
        a.partial_fit(pad)
        b.partial_fit(pk)
    np.testing.assert_allclose(b.alpha_, _pack_alpha(a.alpha_), atol=1e-5)
    np.testing.assert_allclose(b.W_, a.W_, atol=5e-5)
    np.testing.assert_allclose(b.decision_function(pk), _pack_alpha(a.decision_function(pad)), atol=5e-5)
    assert b.score(pk) == pytest.approx(a.score(pad), abs=1e-6)


_FOUR = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, sys
    import numpy as np
    sys.path.insert(0, {repo!r} + "/src")
    sys.path.insert(0, {repo!r} + "/tests")
    from test_packed import _tasks
    from repro.core import DMTRLEstimator, pack_tasks
    from repro.launch.mesh import make_mesh

    pk = pack_tasks(*_tasks())
    kw = dict(loss="hinge", lam=1e-3, outer_iters=2, rounds=3,
              solver={solver!r}, block_size=32, seed=4)
    out = {{}}
    for chips in (1, 4):
        est = DMTRLEstimator(engine="distributed", mesh=make_mesh((chips,), ("data",)), **kw)
        est.fit(pk)
        out[chips] = (est.W_, est.alpha_, est.history_["gap"])
    err = [float(np.max(np.abs(a - b))) for a, b in zip(out[1], out[4])]
    print(json.dumps({{"W": err[0], "alpha": err[1], "gap": err[2],
                       "rows": out[4][1].shape[0]}}))
    """
)


@pytest.mark.parametrize("solver", ["naive", "block_gram"])
def test_packed_fit_on_four_devices_matches_one(solver):
    """6 tasks of 8 to 300 rows on 4 workers: the task axis pads to 8, each
    worker's rows to the largest worker's total (8 + 300)."""
    out = subprocess.run(
        [sys.executable, "-c", _FOUR.format(repo=REPO, solver=solver)],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
    )
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["rows"] == sum(SIZES)
    assert r["W"] < 5e-5 and r["alpha"] < 1e-5 and r["gap"] < 1e-5, r


def test_worker_layout_deals_tasks_in_ranges():
    dst, rows, n = worker_layout(np.array(SIZES), 4)
    assert n.tolist() == list(SIZES) + [1, 1]
    assert rows == 8 + 300  # worker 0's tasks 0 and 1 hold the most rows
    # worker 1 holds tasks 2 and 3 from its first row on
    assert dst[8 + 300] == rows and dst[8 + 300 + 41] == rows + 41
    assert sorted(set(dst.tolist())) == dst.tolist()


@pytest.mark.parametrize("solver", ["pallas_round", "pallas_block"])
def test_pallas_backends_refuse_packed_data(both, one_device_mesh, solver):
    est = DMTRLEstimator(
        engine="distributed", mesh=one_device_mesh, solver=solver, outer_iters=1, rounds=1
    )
    with pytest.raises(ValueError, match="refuses packed task storage"):
        est.fit(both[1])


@pytest.mark.parametrize("engine", ["reference", "async"])
def test_other_engines_refuse_packed_data(both, engine):
    est = DMTRLEstimator(engine=engine, outer_iters=1, rounds=1)
    with pytest.raises(ValueError, match="packed task storage"):
        est.fit(both[1])


def test_one_device_shard_copies_nothing(both, one_device_mesh):
    pk = both[1]
    out, m, d = shard_mtl_data(pk, one_device_mesh, MeshAxes())
    assert (m, d, out.workers) == (6, D, 1)
    for a, b in ((out.x, pk.x), (out.y, pk.y), (out.mask, pk.mask)):
        assert a.unsafe_buffer_pointer() == b.unsafe_buffer_pointer()


def test_row_gauges_count_stored_and_real_rows(both, one_device_mesh):
    from repro.obs.metrics import get_registry

    pad, pk = both
    gauge = get_registry().gauge("repro_engine_data_rows", labels=("layout", "kind"))
    for data in both:
        DMTRLEstimator(
            engine="distributed", mesh=one_device_mesh, outer_iters=1, rounds=1, block_size=32
        ).fit(data)
    assert gauge.value(layout="padded", kind="stored") == 6 * 300
    assert gauge.value(layout="packed", kind="stored") == sum(SIZES)
    assert gauge.value(layout="padded", kind="real") == sum(SIZES)
    assert gauge.value(layout="packed", kind="real") == sum(SIZES)


def test_shard_span_names_the_layout(both, one_device_mesh, tmp_path):
    """Under a profiler session, each fit's ``driver.shard`` span carries
    ``layout``: ``padded`` or ``packed``."""
    import glob

    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for data in both:
            DMTRLEstimator(
                engine="distributed", mesh=one_device_mesh, outer_iters=1, rounds=1, block_size=32
            ).fit(data)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    layouts = [
        dict(ev.stats)["layout"]
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
        if ev.name == "driver.shard"
    ]
    assert sorted(layouts) == ["packed", "padded"]
