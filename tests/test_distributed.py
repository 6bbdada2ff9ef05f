"""Distributed (shard_map) DMTRL == single-process reference.

The 1-device mesh case runs in-process; the real multi-device cases run in a
subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8 (device
count must be set before jax initializes).
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import MeshAxes, fit, fit_distributed

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
    from repro.core import DMTRLConfig, MeshAxes, fit, fit_distributed
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


@pytest.mark.slow
def test_model_axis_hoisted_block_gram_exact():
    """the hoisted block-Gram distributed round (dist_block_hoisted) must
    produce the same iterates as the reference — guards the refactor of the
    round body into local-solve/server-reduce pieces."""
    r = _run_subproc(
        "squared", "(4, 2)", '("data", "model")',
        'dict(data="data", model="model")',
        extra='dict(dist_block_hoisted=True)',
    )
    assert r["werr"] < 5e-4, r
    assert r["serr"] < 5e-5, r


@pytest.mark.slow
def test_pod_axis_converges():
    """intra-task sample partitioning over 'pod': iterates differ from the
    single-process reference (finer CoCoA blocks) but the gap must shrink."""
    r = _run_subproc(
        "hinge", "(2, 4)", '("pod", "data")', 'dict(data="data", pod="pod")'
    )
    assert r["gap_last"] < r["gap_first"] * 0.8, r
