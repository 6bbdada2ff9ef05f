import os
from pathlib import Path

import jax
import pytest

# persistent XLA compilation cache: the suite is compile-dominated on CPU,
# so re-runs skip most of the wall clock. JAX_COMPILATION_CACHE_DIR wins
# when it is set (an empty value turns the cache off); otherwise the cache
# lives at a fixed path inside the checkout. The environment carries it to
# the subprocess-based mesh tests.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR", str(Path(__file__).resolve().parents[1] / ".jax_cache")
)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
jax.config.update("jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test (subprocess/convergence)"
    )
    config.addinivalue_line(
        "markers", "load: serving load-generator test (scheduler under "
        "queued traffic)"
    )


def fast_arch_params(fast):
    """Parametrize over all arch ids, marking everything outside ``fast``
    as slow. Asserts the fast ids actually exist so a rename in
    configs/base.py fails loudly instead of silently demoting archs."""
    from repro.configs import ARCH_IDS

    unknown = set(fast) - set(ARCH_IDS)
    assert not unknown, f"fast arch ids not in ARCH_IDS: {sorted(unknown)}"
    return [
        a if a in fast else pytest.param(a, marks=pytest.mark.slow)
        for a in ARCH_IDS
    ]


# Small shared problems: fast tests should reuse these instead of building
# their own larger instances (keeps the default tier-1 run under ~2 min).
@pytest.fixture(scope="session")
def small_problem():
    from repro.data.synthetic import synthetic

    return synthetic(1, m=4, d=16, n_train_avg=40, n_test_avg=10, seed=1)


@pytest.fixture(scope="session")
def small_cfg():
    from repro.core import DMTRLConfig

    return DMTRLConfig(
        loss="hinge",
        lam=1e-3,
        outer_iters=2,
        rounds=3,
        local_iters=32,
        solver="block_gram",
        block_size=32,
        seed=0,
    )


@pytest.fixture(scope="session")
def one_device_mesh():
    from repro.launch.mesh import make_mesh

    return make_mesh((1,), ("data",))
