"""Bytes of the objective pass and of W(alpha) over packed rows, from
shapes alone.

Both programs read every stored task row: W(alpha) = Sigma B / lambda needs
b_i = X_i^T alpha_i / n_i, one pass over the rows; the objectives need B
and then the predictions x . w_i(alpha) of every row, which depend on all
of B, so two passes. Their other inputs (alpha, y, W, Sigma) are under a
thousandth of the rows at MDS's shape and are not counted. The least data
a pass must read is every real row once: sum_i n_i * d * 4 bytes in
float32.
"""
from __future__ import annotations

# passes over the rows of each packed program, by its module name in the
# device trace (core/dmtrl.py)
PASSES = {"jit_packed_objectives": 2, "jit_packed_w_from_alpha": 1}


def pass_bytes(d: int, samples: int, itemsize: int = 4) -> float:
    """``samples`` is the number of real rows over all tasks (sum n_i)."""
    return float(samples) * d * itemsize
