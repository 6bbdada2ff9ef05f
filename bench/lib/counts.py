"""Operations and bytes of one local SDCA round, from shapes alone.

The same counts hold whatever implements the round (block-Gram, the fused
Pallas kernel, a future streamed kernel), so a roofline share read against
them compares implementations on one yardstick.

Per coordinate update of Algorithm 2 the work is three d-length inner
products (w.x, x.r, x.x) and one axpy (r += delta x): 4 d multiply-adds
counted as 4 d FLOPs, as the paper's cost model does. A round makes H
updates in every real task. The least data a round must read is every
real task's rows once: sum_i n_i * d * 4 bytes in float32.
"""
from __future__ import annotations


def round_flops(d: int, H: int, tasks: int) -> float:
    return 4.0 * d * H * tasks


def round_bytes(d: int, samples: int, itemsize: int = 4) -> float:
    """``samples`` is the number of real rows over all tasks (sum n_i)."""
    return float(samples) * d * itemsize


def least_time(flops: float, nbytes: float, peak: dict) -> tuple:
    """(seconds, bound): the larger of FLOPs over peak and bytes over HBM
    bandwidth, and which of the two it was."""
    t_flops = flops / peak["bf16_flops"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    if t_bytes >= t_flops:
        return t_bytes, "bytes"
    return t_flops, "flops"
