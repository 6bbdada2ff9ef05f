"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpreted on
the CPU, compiled on a TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import interpret_mode
from repro.kernels.flash import flash_attention
from repro.kernels.flash.ops import flash_attention_bshd
from repro.kernels.flash.ref import attention_ref
from repro.kernels.sdca import sdca_block_kernel, sdca_round_kernel
from repro.kernels.sdca.ref import sdca_block_ref, sdca_round_ref
from repro.kernels.ssd.ops import ssd_forward
from repro.kernels.ssd.ref import chunk_ref, naive_recurrence
from repro.kernels.ssd import ssd_chunk_kernel


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "B,H,S,HD,causal,window,dtype",
    [
        (2, 3, 256, 64, True, 0, jnp.float32),
        (1, 2, 128, 32, True, 48, jnp.float32),
        pytest.param(2, 2, 256, 64, False, 0, jnp.float32,
                     marks=pytest.mark.slow),
        pytest.param(1, 4, 512, 128, True, 0, jnp.float32,
                     marks=pytest.mark.slow),
        pytest.param(2, 2, 256, 64, True, 0, jnp.bfloat16,
                     marks=pytest.mark.slow),
        (1, 1, 64, 16, True, 16, jnp.float32),
    ],
)
def test_flash_vs_ref(B, H, S, HD, causal, window, dtype):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, S, HD), dtype)
    k = jax.random.normal(ks[1], (B, H, S, HD), dtype)
    v = jax.random.normal(ks[2], (B, H, S, HD), dtype)
    out = flash_attention(
        q, k, v, causal, window, block_q=64, block_k=64,
        interpret=interpret_mode(),
    )
    ref = attention_ref(q, k, v, causal, window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


def test_flash_bshd_wrapper_with_padding():
    key = jax.random.PRNGKey(1)
    B, S, H, HD = 2, 200, 2, 64  # S not a multiple of the block
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, HD))
    k = jax.random.normal(ks[1], (B, S, H, HD))
    v = jax.random.normal(ks[2], (B, S, H, HD))
    out = flash_attention_bshd(q, k, v, causal=True, block_q=64, block_k=64)
    ref = attention_ref(
        jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1), jnp.moveaxis(v, 2, 1), True, 0
    )
    np.testing.assert_allclose(
        np.asarray(jnp.moveaxis(out, 2, 1)), np.asarray(ref), atol=2e-5
    )


# ---------------------------------------------------------------------------
# sdca block kernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("loss", ["hinge", "squared", "smoothed_hinge"])
@pytest.mark.parametrize(
    "B,d",
    [
        (16, 50),
        (32, 130),
        pytest.param(64, 1024, marks=pytest.mark.slow),
        pytest.param(128, 700, marks=pytest.mark.slow),
    ],
)
def test_sdca_kernel_vs_ref(loss, B, d):
    key = jax.random.PRNGKey(B * d)
    ks = jax.random.split(key, 6)
    xb = jax.random.normal(ks[1], (B, d))
    w = 0.1 * jax.random.normal(ks[2], (d,))
    r = 0.05 * jax.random.normal(ks[3], (d,))
    y = (
        jnp.sign(jax.random.normal(ks[4], (B,)))
        if loss != "squared"
        else jax.random.normal(ks[4], (B,))
    )
    at0 = (
        y * jnp.abs(0.4 * jax.random.normal(ks[5], (B,))).clip(0, 1)
        if loss != "squared"
        else 0.4 * jax.random.normal(ks[5], (B,))
    )
    cb = jax.random.randint(ks[0], (B,), 0, max(B // 2, 1))  # force duplicates
    kappa = jnp.float32(0.9)
    dk = sdca_block_kernel(
        xb, w, r, at0, y, cb, kappa, loss, interpret=interpret_mode(), d_tile=256
    )
    dr = sdca_block_ref(xb, w, r, at0, y, cb, kappa, loss)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dr), atol=5e-6)


@pytest.mark.parametrize("loss", ["hinge", "squared", "smoothed_hinge"])
@pytest.mark.parametrize(
    "n,d,H,block",
    [
        (60, 40, 64, 16),
        (100, 30, 96, 32),
        pytest.param(256, 130, 256, 64, marks=pytest.mark.slow),
    ],
)
def test_sdca_round_kernel_vs_ref(loss, n, d, H, block):
    """Fused round kernel == sequential coordinate-at-a-time oracle,
    including the on-device coordinate sampling and duplicate handling."""
    key = jax.random.PRNGKey(n * d + H)
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (n, d))
    y = (
        jnp.sign(jax.random.normal(ks[1], (n,)))
        if loss != "squared"
        else jax.random.normal(ks[1], (n,))
    )
    alpha = (
        y * jnp.abs(0.4 * jax.random.normal(ks[2], (n,))).clip(0, 1)
        if loss != "squared"
        else 0.4 * jax.random.normal(ks[2], (n,))
    )
    w = 0.1 * jax.random.normal(ks[3], (d,))
    u = jax.random.uniform(ks[4], (H,))
    n_i = jnp.int32(max(n - 7, 1))  # padded tail + duplicate draws
    kappa = jnp.float32(0.9)
    dak, rk = sdca_round_kernel(
        x, y, alpha, w, u, n_i, kappa, loss, interpret=interpret_mode(), block=block
    )
    dar, rr = sdca_round_ref(x, y, alpha, w, u, n_i, kappa, loss)
    np.testing.assert_allclose(np.asarray(dak), np.asarray(dar), atol=1e-5)
    np.testing.assert_allclose(np.asarray(rk), np.asarray(rr), atol=1e-5)
    # padded coordinates must never be touched
    assert np.all(np.asarray(dak)[int(n_i):] == 0.0)


# ---------------------------------------------------------------------------
# ssd chunk kernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "B,L,H,P,N,chunk",
    [
        pytest.param(2, 96, 4, 16, 8, 32, marks=pytest.mark.slow),
        (1, 64, 2, 32, 16, 16),
        pytest.param(2, 130, 3, 8, 4, 32, marks=pytest.mark.slow),
    ],
)
def test_ssd_forward_vs_naive(B, L, H, P, N, chunk):
    key = jax.random.PRNGKey(L)
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[1], (B, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (B, L, H))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[3], (H,)))
    Bm = jax.random.normal(ks[4], (B, L, H, N)) * 0.3
    Cm = jax.random.normal(ks[5], (B, L, H, N)) * 0.3
    Y0, S0 = naive_recurrence(x, dt, A, Bm, Cm)
    Y, S = ssd_forward(x, dt, A, Bm, Cm, chunk=chunk)
    np.testing.assert_allclose(np.asarray(Y), np.asarray(Y0), atol=2e-4)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S0), atol=2e-4)


def test_ssd_chunk_kernel_matches_chunk_ref():
    key = jax.random.PRNGKey(9)
    B, H, nc, Q, P, N = 2, 3, 4, 16, 8, 8
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[1], (B, H, nc, Q, P))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (B, H, nc, Q))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[3], (H,)))
    Bm = jax.random.normal(ks[4], (B, H, nc, Q, N)) * 0.3
    Cm = jax.random.normal(ks[5], (B, H, nc, Q, N)) * 0.3
    Yk, Sk, ak = ssd_chunk_kernel(x, dt, A, Bm, Cm, interpret=interpret_mode())
    Yr, Sr, ar = chunk_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(Yk), np.asarray(Yr), atol=1e-5)
    # kernel S is (N, P); ref is (N, P) too via einsum 'bhcqn,bhcqp->bhcnp'
    np.testing.assert_allclose(np.asarray(Sk), np.asarray(Sr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(ak), np.asarray(ar), atol=1e-6)


@pytest.mark.slow
def test_model_ssd_matches_kernel_pipeline():
    """models/ssm.ssd_chunked and kernels/ssd.ops.ssd_forward agree."""
    from repro.models.ssm import ssd_chunked

    key = jax.random.PRNGKey(4)
    B, L, H, P, N = 2, 80, 2, 16, 8
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[1], (B, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (B, L, H))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[3], (H,)))
    Bm = jax.random.normal(ks[4], (B, L, H, N)) * 0.3
    Cm = jax.random.normal(ks[5], (B, L, H, N)) * 0.3
    Y1, S1 = ssd_chunked(x, dt, A, Bm, Cm, chunk=16)
    Y2, S2 = ssd_forward(x, dt, A, Bm, Cm, chunk=16)
    np.testing.assert_allclose(np.asarray(Y1), np.asarray(Y2), atol=2e-5)
    np.testing.assert_allclose(np.asarray(S1), np.asarray(S2), atol=2e-5)
