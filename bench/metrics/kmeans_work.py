"""The work of the k-means assignment (``kmeans_assign``), from the
problem's shapes, whatever implements it; ``work.roofline`` turns it into
a share of the chip's roofline.

* One assignment pass of N points against K centres in D dimensions:
  ops = 3 * N * K * D (a multiply, a subtract or add, and the sum of the
  distance's terms), against the bf16 peak; bytes = the points and
  centres read once (float32) and the assignment and distance of each
  point written once (int32 and float32).  N, K and D are the problem's,
  not the kernel's padded tiles.
* A k-means job of ``iters`` Lloyd steps makes ``iters + 1`` passes over
  every point: one per step and the final assignment.  (The k-means++
  seeding computes its distances outside the kernel.)
"""

from __future__ import annotations


def kmeans_assign(n: int, k: int, d: int) -> tuple[float, float]:
    """(ops, bytes) of one assignment pass."""
    return 3.0 * n * k * d, 4.0 * n * d + 4.0 * k * d + 8.0 * n


def kmeans_job(n: int, k: int, d: int, iters: int) -> tuple[float, float]:
    """(ops, bytes) of the assignment passes of one k-means job over all
    ``n`` points."""
    ops, nbytes = kmeans_assign(n, k, d)
    return (iters + 1) * ops, (iters + 1) * nbytes
