"""Share of the round programs' device time spent in collectives
(all-gather, all-reduce, ...) on each device; the worst device is
reported."""
from bench.lib.trace import ROUND_PROGRAM


def read(run):
    tr = run.trace
    if tr is None or not tr.devices:
        return None
    shares = []
    for d in tr.devices:
        rounds = d.module_intervals(ROUND_PROGRAM)
        total = sum(e - s for s, e in rounds)
        coll = d.collective_ns(within=rounds)
        if total > 0 and coll > 0:
            shares.append(100.0 * coll / total)
    return max(shares) if shares else None
