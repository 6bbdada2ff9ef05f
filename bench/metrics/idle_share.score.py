"""Share of the scoring window in which no operation ran on the device
(profiler trace)."""
from bench.lib.trace import idle_share


def read(run):
    return idle_share(run.trace)
