"""The main-path kernels and round compile for a TPU v5e at real widths.

Nothing runs: each test compiles for a v5e chip that is described, not
attached, so what the chip's compiler (Mosaic for the Pallas kernels)
refuses fails here at no chip time. The topology is described inside a
module fixture, never while a module is imported, and the tests skip where
it cannot be described. The persistent compilation cache is off around
these compiles: an entry written for a described chip cannot be read back
without one.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.dmtrl import DMTRLConfig, make_w_step_round
from repro.core.mtl_data import MTLData
from repro.kernels.sdca import sdca_block_kernel, sdca_round_kernel

MNIST = dict(m=10, n_max=12000, d=784)  # mnist_like(scale=1.0)
SCHOOL = dict(m=139, n_max=106, d=28)  # school_like() train split


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory placed on one described v5e chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda *shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip
    )


@pytest.fixture(autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def test_round_kernel_compiles_at_school_width(spec):
    """pallas_round's kernel, vmapped over all 139 School tasks as the
    engines call it, one 128-coordinate round in blocks of 64."""
    m, n, d, H = SCHOOL["m"], SCHOOL["n_max"], SCHOOL["d"], 128

    def round_all(x, y, alpha, w, u, n_i, kappa):
        return jax.vmap(
            lambda *a: sdca_round_kernel(*a, "squared", interpret=False, block=64)
        )(x, y, alpha, w, u, n_i, kappa)

    compiled = jax.jit(round_all).lower(
        spec(m, n, d), spec(m, n), spec(m, n), spec(m, d), spec(m, H),
        spec(m, dtype=jnp.int32), spec(m),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_block_kernel_compiles_at_mnist_width(spec):
    """pallas_block's kernel for one B=64 block at d=784 (two d tiles),
    vmapped over the 10 MNIST tasks."""
    m, d, B = MNIST["m"], MNIST["d"], 64

    def block_all(xb, w, r, at0, y, cb, kappa):
        return jax.vmap(
            lambda *a: sdca_block_kernel(*a, "hinge", interpret=False)
        )(xb, w, r, at0, y, cb, kappa)

    compiled = jax.jit(block_all).lower(
        spec(m, B, d), spec(m, d), spec(m, d), spec(m, B), spec(m, B),
        spec(m, B, dtype=jnp.int32), spec(m),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_block_gram_round_compiles_at_mnist_width(spec):
    """The jnp block_gram communication round over the whole MNIST shape
    (H = n_max = 12000 rounds up to 12032). The data is an argument of the
    program: x alone is 376 MB, and as a closed-over constant it would be
    baked into the executable."""
    m, n, d = MNIST["m"], MNIST["n_max"], MNIST["d"]
    cfg = DMTRLConfig(loss="hinge", local_iters=n)
    data = MTLData(spec(m, n, d), spec(m, n), spec(m, n), spec(m, dtype=jnp.int32))
    compiled = jax.jit(make_w_step_round(cfg, n, rho=1.0)).lower(
        data, spec(m, n), spec(m, d), spec(m, m), spec(2, dtype=jnp.uint32)
    ).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    assert compiled.memory_analysis().argument_size_in_bytes >= m * n * d * 4
