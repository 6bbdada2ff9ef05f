"""Share of the roofline reached by the packed objective pass and W(alpha):
their least time, the passes over the rows each call makes times the
bytes of one pass over HBM bandwidth (bench/lib/pass_counts.py), summed
over the calls the trace holds, over the device time of those programs.
None where the trace holds neither program (the padded layout, or a
program without packed task storage)."""
from bench.lib.pass_counts import PASSES, pass_bytes


def read(run):
    c, tr = run.counters, run.trace
    if tr is None or not tr.devices or "samples" not in c:
        return None
    one_pass = pass_bytes(c["d"], c["samples"]) / run.chips / run.peak["hbm_bytes_per_s"]
    shares = []
    for d in tr.devices:
        least = device_ns = 0.0
        for program, passes in PASSES.items():
            calls = d.module_intervals(program)
            least += passes * one_pass * len(calls)
            device_ns += sum(e - s for s, e in calls)
        if device_ns > 0:
            shares.append(100.0 * least / (device_ns * 1e-9))
    return sum(shares) / len(shares) if shares else None
