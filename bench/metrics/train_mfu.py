"""The whole fit's share of the chips' bf16 peak: the rounds' algorithmic
FLOPs (bench/lib/counts.py) over window x chips x peak. Only the rounds'
work counts, none of the objectives, Omega-steps or W(alpha)."""
from bench.lib.counts import round_flops


def read(run):
    c = run.counters
    if "rounds" not in c:
        return None
    flops = round_flops(c["d"], c["H"], c["tasks"]) * c["rounds"]
    return 100.0 * flops / (run.window_s * run.chips * run.peak["bf16_flops"])
