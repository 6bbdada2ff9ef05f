"""Distributed (shard_map) DMTRL == single-process reference.

The 1-device mesh case runs in-process; the real multi-device cases run in a
subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8 (device
count must be set before jax initializes).
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.distributed import MeshAxes, fit_distributed
from repro.core.dmtrl import fit

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_one_device_mesh_equals_reference(
    small_problem, small_cfg, one_device_mesh
):
    res = fit(small_cfg, small_problem.train)
    W, sigma, _, hist = fit_distributed(
        small_cfg, small_problem.train, one_device_mesh, MeshAxes(data="data")
    )
    np.testing.assert_allclose(W, np.asarray(res.W), atol=2e-4)
    np.testing.assert_allclose(sigma, np.asarray(res.sigma), atol=1e-5)


def test_driver_spans_reach_the_profiler(
    small_problem, small_cfg, one_device_mesh, tmp_path
):
    """Under a profiler session, one fit through the estimator writes, on
    the host plane and inside ``driver.engine_run``: one ``driver.shard``,
    ``driver.rho`` and ``driver.omega_step`` each outer iteration,
    ``driver.round`` and ``driver.objectives`` each round, one
    ``driver.result``."""
    import collections
    import glob

    import jax
    from jax.profiler import ProfileData

    from repro.core import DMTRLEstimator

    est = DMTRLEstimator(
        engine="distributed", mesh=one_device_mesh, config=small_cfg
    )
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        est.fit(small_problem.train)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    spans = [
        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
        if ev.name.startswith("driver.")
    ]
    P, T = small_cfg.outer_iters, small_cfg.rounds
    count = collections.Counter(n for n, _, _, _ in spans)
    assert count == {
        "driver.engine_run": 1, "driver.shard": 1, "driver.rho": P,
        "driver.round": P * T, "driver.objectives": P * T,
        "driver.omega_step": P, "driver.result": 1,
    }
    rounds = sorted((a["outer"], a["round"]) for n, _, _, a in spans if n == "driver.round")
    assert rounds == [(p, t) for p in range(P) for t in range(T)]
    assert sorted(a["outer"] for n, _, _, a in spans if n == "driver.omega_step") == list(range(P))
    ((_, lo, hi, _),) = [s for s in spans if s[0] == "driver.engine_run"]
    assert all(lo <= s and e <= hi for _, s, e, _ in spans)


def _program_counts():
    from repro.obs.metrics import get_registry

    counter = get_registry().counter(
        "repro_engine_driver_programs_total", labels=("program", "outcome")
    )
    return {
        (p, o): counter.value(program=p, outcome=o)
        for p in ("round", "objectives", "w_from_alpha")
        for o in ("built", "reused")
    }


def _programs_since(before):
    return {k: v - before[k] for k, v in _program_counts().items() if v > before[k]}


@pytest.fixture
def fresh_programs():
    """Empty the driver's program memos, so a test sees its own builds."""
    from repro.core import distributed, dmtrl

    for memo in (
        distributed._round_program,
        dmtrl._objectives_program,
        dmtrl._w_from_alpha_program,
    ):
        memo.cache_clear()


@pytest.mark.parametrize("solver", ["block_gram", "pallas_round"])
def test_refit_lowers_nothing(small_problem, small_cfg, one_device_mesh, solver):
    """A second fit of same-shaped data through the estimator reuses every
    program of the first: JAX reports no lowering to MLIR while it runs."""
    import jax

    from repro.core import DMTRLEstimator

    cfg = dataclasses.replace(small_cfg, solver=solver)
    est = DMTRLEstimator(engine="distributed", mesh=one_device_mesh, config=cfg)
    est.fit(small_problem.train)
    lowered = []

    def listen(event, duration, **_):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        est.fit(small_problem.train)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert lowered == []


def test_round_program_is_reused_across_rho(
    small_problem, small_cfg, one_device_mesh, fresh_programs
):
    """Two datasets of one shape give different rho in every outer
    iteration and fit; one round program serves them all, and each fit
    still equals the single-process reference."""
    import jax

    from repro.core.mtl_data import MTLData

    a = small_problem.train
    noise = jax.random.uniform(jax.random.PRNGKey(7), a.x.shape, a.x.dtype)
    b = MTLData(a.x * (0.5 + noise), a.y, a.mask, a.n)
    cfg = dataclasses.replace(small_cfg, outer_iters=3)
    before = _program_counts()
    rhos = []
    for data in (a, b):
        res = fit(cfg, data)
        rhos.append(res.rho_per_outer)
        W, sigma, _, _ = fit_distributed(
            cfg, data, one_device_mesh, MeshAxes(data="data")
        )
        np.testing.assert_allclose(W, np.asarray(res.W), atol=2e-4)
        np.testing.assert_allclose(sigma, np.asarray(res.sigma), atol=1e-5)
    # every rho differs but the first, which both fits take from Sigma = I/m
    assert len(set(rhos[0] + rhos[1])) == 2 * cfg.outer_iters - 1
    counts = _programs_since(before)
    assert counts[("round", "built")] == 1
    assert counts[("round", "reused")] == 2 * cfg.outer_iters - 1


def test_program_counter_reads_builds_and_reuses(
    small_problem, small_cfg, one_device_mesh, fresh_programs, monkeypatch
):
    """Two fits of 5 outer iterations build each program once; a fault
    planted in the round's local solve after them builds a new round
    program, so a sound program built earlier cannot hide it."""
    from repro.core import DMTRLEstimator

    monkeypatch.syspath_prepend(REPO)
    from bench.lib import faults

    cfg = dataclasses.replace(small_cfg, outer_iters=5, rounds=2)
    est = DMTRLEstimator(engine="distributed", mesh=one_device_mesh, config=cfg)
    before = _program_counts()
    sound = [est.fit(small_problem.train).W_ for _ in range(2)]
    assert _programs_since(before) == {
        ("round", "built"): 1, ("round", "reused"): 9,
        ("objectives", "built"): 1, ("objectives", "reused"): 1,
        ("w_from_alpha", "built"): 1, ("w_from_alpha", "reused"): 1,
    }
    np.testing.assert_array_equal(sound[0], sound[1])

    faults.plant("half", monkeypatch.setattr)
    before = _program_counts()
    broken = est.fit(small_problem.train).W_
    counts = _programs_since(before)
    assert counts[("round", "built")] == 1
    assert counts[("round", "reused")] == 4
    assert np.max(np.abs(broken - sound[0])) > 1e-3


@pytest.mark.parametrize("engine", ["distributed", "async"])
def test_explicit_mesh_is_rejected(small_problem, small_cfg, engine):
    """jax.make_mesh's default Explicit axes break the engines' host-side
    Sigma algebra; both mesh engines refuse such a mesh by name."""
    import jax
    from jax.sharding import AxisType

    from repro.core import DMTRLEstimator

    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Explicit,))
    est = DMTRLEstimator(engine=engine, mesh=mesh, config=small_cfg)
    with pytest.raises(ValueError, match=r"Auto-typed .*'data': 'Explicit'"):
        est.fit(small_problem.train)


_SUBPROC = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    import jax, numpy as np
    sys.path.insert(0, {repo!r} + "/src")
    from repro.core.dmtrl import DMTRLConfig, fit
    from repro.core.distributed import MeshAxes, fit_distributed
    from repro.launch.mesh import make_mesh
    from repro.data.synthetic import synthetic

    sp = synthetic(1, m={m}, d=32, n_train_avg=70, n_test_avg=20, seed=2)
    cfg = DMTRLConfig(loss={loss!r}, lam=1e-3, outer_iters=2, rounds=3,
                      local_iters=64, solver="block_gram", block_size=32, seed=0,
                      **{extra})
    res = fit(cfg, sp.train)
    mesh = make_mesh({mesh_shape}, {mesh_axes})
    W, sigma, _, hist = fit_distributed(cfg, sp.train, mesh, MeshAxes(**{axes_kw}))
    werr = float(np.max(np.abs(W - np.asarray(res.W))))
    serr = float(np.max(np.abs(sigma - np.asarray(res.sigma))))
    gap_last = float(hist["gap"][-1]); gap_first = float(hist["gap"][0])
    print(json.dumps({{"werr": werr, "serr": serr,
                       "gap_first": gap_first, "gap_last": gap_last}}))
    """
)


def _run_subproc(loss, mesh_shape, mesh_axes, axes_kw, extra="dict()", m=8):
    code = _SUBPROC.format(
        repo=REPO, loss=loss, mesh_shape=mesh_shape, mesh_axes=mesh_axes,
        axes_kw=axes_kw, extra=extra, m=m,
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=900,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_eight_workers_data_parallel_exact():
    """8 tasks over 8 workers — the paper's one-task-per-worker setting."""
    r = _run_subproc("hinge", "(8,)", '("data",)', 'dict(data="data")')
    assert r["werr"] < 5e-4, r
    assert r["serr"] < 5e-5, r


def test_padded_task_axis_matches_reference():
    """6 tasks over 4 workers pad the task axis to 8. The real tasks must
    start from the paper's Sigma = I/6, not I/8 over the padded count (the
    MNIST shape on four chips pads 10 tasks to 12)."""
    r = _run_subproc("hinge", "(4,)", '("data",)', 'dict(data="data")', m=6)
    assert r["werr"] < 5e-4, r
    assert r["serr"] < 5e-5, r


@pytest.mark.slow
def test_data_plus_model_axes_exact():
    """tasks over 'data', feature dim over 'model' (block-Gram psums)."""
    r = _run_subproc(
        "squared", "(4, 2)", '("data", "model")',
        'dict(data="data", model="model")',
    )
    assert r["werr"] < 5e-4, r
    assert r["serr"] < 5e-5, r


@pytest.mark.parametrize("solver", ["naive", "block_gram"])
def test_model_axis_round_equals_naive_on_one_device(small_problem, solver):
    """With a ``model`` axis (here of size 1) the round runs the named
    backend with its d-contractions psum'ed over it, and gives naive's
    dual variables on a data-only mesh: 128 draws a round in blocks of 64
    from tasks of about 40 samples, so most blocks draw a coordinate
    several times."""
    import jax

    from repro.core import DMTRLConfig
    from repro.core.distributed import init_state, make_distributed_round, shard_mtl_data
    from repro.launch.mesh import make_mesh

    base = dict(loss="hinge", lam=1e-3, local_iters=128, block_size=64)
    runs = (
        (DMTRLConfig(solver="naive", **base), make_mesh((1,), ("data",)), MeshAxes(data="data")),
        (
            DMTRLConfig(solver=solver, **base),
            make_mesh((1, 1), ("data", "model")),
            MeshAxes(data="data", model="model"),
        ),
    )
    out = []
    for cfg, mesh, axes in runs:
        data, m, d = shard_mtl_data(small_problem.train, mesh, axes)
        st = init_state(data, mesh, axes, m, d)
        rf = make_distributed_round(cfg, mesh, axes, m, data.n_max, d, 2.0)
        alpha, W = st.alpha, st.W
        for t in range(2):
            key = jax.random.PRNGKey(7 + t)
            alpha, W = rf(data.x, data.y, data.mask, data.n, alpha, W, st.sigma, key)
        out.append((np.asarray(alpha), np.asarray(W)))
    (a_naive, w_naive), (a_model, w_model) = out
    assert int(np.max(small_problem.train.n)) < 64
    np.testing.assert_allclose(a_model, a_naive, atol=2e-5)
    np.testing.assert_allclose(w_model, w_naive, atol=2e-5)
    assert np.abs(a_naive).max() > 1e-3  # the rounds moved


def test_model_axis_refuses_pallas_backend(small_problem, small_cfg):
    """A Pallas kernel computes its own d-contractions and cannot psum them
    over a ``model`` axis: the engine raises and names block_gram instead
    of running another form of the round."""
    from repro.core import DMTRLEstimator
    from repro.launch.mesh import make_mesh

    est = DMTRLEstimator(
        engine="distributed", mesh=make_mesh((1, 1), ("data", "model")),
        axes=MeshAxes(data="data", model="model"), config=small_cfg,
        solver="pallas_round",
    )
    with pytest.raises(ValueError, match="block_gram"):
        est.fit(small_problem.train)


@pytest.mark.slow
def test_pod_axis_converges():
    """intra-task sample partitioning over 'pod': iterates differ from the
    single-process reference (finer CoCoA blocks) but the gap must shrink."""
    r = _run_subproc(
        "hinge", "(2, 4)", '("pod", "data")', 'dict(data="data", pod="pod")'
    )
    assert r["gap_last"] < r["gap_first"] * 0.8, r
