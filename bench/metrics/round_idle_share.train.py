"""Share of the training window in which the device is idle while the
host driver is inside a round trip: a ``driver.round`` span (the round's
dispatch) or a ``driver.objectives`` span (the objective pass and its
readbacks), less those holding a JAX compile event, which
compile_share.train counts; over the traced window, averaged over the
chips (bench/lib/program_trace.py). None where the program has no such
span."""
from bench.lib import program_trace


def read(run):
    return program_trace.round_idle_share(run.trace, program_trace.of(run.trace))
