"""Share of the training window spent in the Omega-step: from the start of
each ``driver.omega_step`` span to the end of the first ``driver.rho`` or
``driver.result`` span after it (the readback that waits for the step's
device work), less those intervals that hold a JAX compile event, which
compile_share.train counts; the union of these intervals over the traced
window (bench/lib/program_trace.py). None where the program has no such
span."""
from bench.lib import program_trace


def read(run):
    return program_trace.omega_share(run.trace, program_trace.of(run.trace))
