"""Regenerate the golden async event histories (tests/golden/async_histories.json).

The goldens pin the *integer* event bookkeeping of the bounded-staleness
protocol — per-commit (worker, round, staleness, lag, tick) sequences plus
the tau trace and objective-sample indices — for a fixed set of configs.
Integers are platform-independent (unlike float iterates), so the fixture
can be committed and replayed on any host: the ``simulated`` transport must
reproduce every sequence bit-exactly after any refactor of the engine.

Recorded from the pre-transport-refactor engine (PR 3 tree). Regenerate
only if the *protocol semantics* deliberately change:

    PYTHONPATH=src python tests/golden/gen_async_golden.py
"""
import json
import os
import subprocess
import sys
import textwrap

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))

# keys whose values are integral and platform-stable
INT_KEYS = (
    "round", "tick", "min_round",
    "w_worker", "w_round", "w_staleness", "w_lag", "w_tick",
    "tau_trace",
)

# name -> (devices, problem kwargs, DMTRLConfig + AsyncOptions kwargs)
CASES = {
    "g1_tau2_omega1": (
        1,
        dict(m=4, d=16, n_train_avg=40, n_test_avg=10, seed=1),
        dict(loss="hinge", lam=1e-3, outer_iters=2, rounds=3, local_iters=32,
             solver="block_gram", block_size=32, seed=0, tau=2,
             omega_delay=1, async_delays=(2,)),
    ),
    "g4_straggler_tau1": (
        4,
        dict(m=4, d=16, n_train_avg=40, n_test_avg=10, seed=3),
        dict(loss="hinge", lam=1e-3, outer_iters=1, rounds=4, local_iters=32,
             solver="block_gram", block_size=32, seed=0, tau=1,
             async_delays=(1, 1, 1, 3)),
    ),
    "g4_straggler_tau4_omega2": (
        4,
        dict(m=4, d=16, n_train_avg=40, n_test_avg=10, seed=3),
        dict(loss="hinge", lam=1e-3, outer_iters=2, rounds=4, local_iters=32,
             solver="block_gram", block_size=32, seed=0, tau=4,
             omega_delay=2, async_delays=(1, 1, 1, 3)),
    ),
    "g4_straggler_tau_auto": (
        4,
        dict(m=4, d=16, n_train_avg=40, n_test_avg=10, seed=3),
        dict(loss="hinge", lam=1e-3, outer_iters=2, rounds=4, local_iters=32,
             solver="block_gram", block_size=32, seed=0, tau="auto",
             async_delays=(1, 1, 1, 3)),
    ),
}

_RUNNER = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
    import json, sys
    import jax, numpy as np
    sys.path.insert(0, {repo!r} + "/src")
    from repro.core import DMTRLConfig, MeshAxes
    from repro.launch.mesh import make_mesh
    from repro.core.async_dmtrl import AsyncOptions, fit_async
    from repro.data.synthetic import synthetic
    prob = {prob!r}
    cfg_kw = {cfg!r}
    # the flat record splits into the algorithm's config and the options
    opt_kw = {{k: cfg_kw.pop(k) for k in ("tau", "omega_delay") if k in cfg_kw}}
    opt_kw["async_delays"] = tuple(cfg_kw.pop("async_delays"))
    sp = synthetic(1, **prob)
    mesh = make_mesh(({devices},), ("data",))
    _, _, _, hist = fit_async(
        DMTRLConfig(**cfg_kw), sp.train, mesh, MeshAxes(data="data"),
        options=AsyncOptions(**opt_kw),
    )
    out = {{k: np.asarray(hist[k]).astype(int).tolist() for k in {keys!r}}}
    print("GOLDEN" + json.dumps(out))
    """
)


def run_case(devices, prob, cfg):
    code = _RUNNER.format(
        devices=devices, repo=REPO, prob=prob, cfg=cfg, keys=INT_KEYS
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=900,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
    )
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("GOLDEN")][-1]
    return json.loads(line[len("GOLDEN"):])


def main():
    golden = {}
    for name, (devices, prob, cfg) in CASES.items():
        print(f"recording {name} (devices={devices}) ...", flush=True)
        golden[name] = {
            "devices": devices,
            "problem": prob,
            "config": {k: list(v) if isinstance(v, tuple) else v
                       for k, v in cfg.items()},
            "history": run_case(devices, prob, cfg),
        }
    path = os.path.join(HERE, "async_histories.json")
    with open(path, "w") as f:
        json.dump(golden, f, indent=1, sort_keys=True)
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
