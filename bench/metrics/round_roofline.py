"""Share of the roofline reached by the local SDCA round: the least time
the chip needs for one round's algorithmic work (bench/lib/counts.py),
times the rounds the trace holds, over the device time of those round
programs."""
from bench.lib.counts import least_time, round_bytes, round_flops
from bench.lib.trace import ROUND_PROGRAM


def read(run):
    c, tr = run.counters, run.trace
    if tr is None or not tr.devices or "H" not in c:
        return None
    least, _ = least_time(
        round_flops(c["d"], c["H"], c["tasks"]),
        round_bytes(c["d"], c["samples"]) / run.chips,
        run.peak,
    )
    shares = []
    for d in tr.devices:
        rounds = d.module_intervals(ROUND_PROGRAM)
        device_ns = sum(e - s for s, e in rounds)
        if device_ns > 0:
            shares.append(100.0 * least * len(rounds) / (device_ns * 1e-9))
    return sum(shares) / len(shares) if shares else None
