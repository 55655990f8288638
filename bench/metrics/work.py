"""The work a kernel call needs, from the problem's shapes, whatever
implements it, and its share of the chip's roofline.

* Support counting (dense formulation): ops = 2 * n_tx * C * n_items,
  against the int8 peak; bytes = the packed transactions and candidates
  read once and the counts written once.
"""

from __future__ import annotations


def support_count(n_tx: int, n_cand: int, n_items: int, words: int) -> tuple[float, float]:
    """(ops, bytes) of counting ``n_cand`` candidates over ``n_tx`` rows."""
    ops = 2.0 * n_tx * n_cand * n_items
    nbytes = 4.0 * (n_tx * words + n_cand * words + n_cand)
    return ops, nbytes


def roofline(ops: float, nbytes: float, seconds: float, peak_ops: float,
             peak_bytes_per_s: float) -> dict:
    """The share of the roofline (the least time, the larger of ops over
    peak and bytes over bandwidth, over the measured time), in %, and
    which of the two bounds it."""
    t_ops, t_bytes = ops / peak_ops, nbytes / peak_bytes_per_s
    return {"value": 100.0 * max(t_ops, t_bytes) / seconds,
            "bound": "compute" if t_ops >= t_bytes else "memory",
            "kernel_s": seconds}
