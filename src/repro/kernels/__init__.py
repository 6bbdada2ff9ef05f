"""Pallas kernels for the compute hot-spots (SDCA local round; flash
attention and SSD for the model zoo)."""
import jax


def interpret_mode() -> bool:
    """Whether Pallas kernels run in the interpreter: only on the CPU, which
    has no Mosaic compiler. On a TPU every kernel compiles."""
    return jax.default_backend() == "cpu"
