"""Share of the training window in which no operation ran on the device,
averaged over the chips (profiler trace)."""
from bench.lib.trace import idle_share


def read(run):
    return idle_share(run.trace)
