"""Keys and seeds derived from the benchmark's ``--seed``, which may exceed
32 bits: ``jax.random.PRNGKey`` keeps only the low 32, so the high part is
folded in."""
from __future__ import annotations


def data_key(seed: int):
    import jax

    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32) if seed >> 32 else key


def fit_seed(seed: int) -> int:
    """The seed the program's coordinate draws start from (a signed 32-bit
    int, as the program's config takes it)."""
    return seed & 0x7FFFFFFF
