"""Points shaped like Fränti's DIM-sets (Fränti, Virmajoki and Hautamäki,
IEEE TPAMI 28(11), 2006; described again in Fränti and Sieranoja,
"K-means properties on six clustering benchmark datasets", Applied
Intelligence 2018): ``n_clusters`` well-separated Gaussian clusters of
equal size in ``dim`` dimensions (``dim032``: 16 clusters, D = 32).

The source publishes 1,024 points and fixes neither where the centres
sit nor how wide the clusters are.  Here the centres are drawn uniformly
in the box ``[box[0], box[1]]^dim`` from the deployment's own
``centre_seed`` (fixed in the configuration), every cluster is an
isotropic Gaussian of standard deviation ``sigma`` in each dimension, and
the run seed draws each point's cluster (equal weights) and its noise.

``rows(data, seed)`` is the generator as the harness finds it by a
configuration's ``data.generator``; ``draw`` also gives each row's true
cluster, for the tests (the harness compares with the plain reference,
never with the truth).  ``TINY`` is the size a CPU test runs it at: 4
sites of 1,000 points, each cut into 20 sub-clusters.
"""

from __future__ import annotations

import numpy as np

BLOCK_ROWS = 1 << 20

TINY = {"n_points": 4000}


def centres(data: dict) -> np.ndarray:
    """The (n_clusters, dim) float32 cluster centres of the deployment."""
    lo, hi = data["box"]
    rng = np.random.default_rng(data["centre_seed"])
    return rng.uniform(lo, hi, (data["n_clusters"], data["dim"])).astype(np.float32)


def draw(data: dict, seed) -> tuple[np.ndarray, np.ndarray]:
    """(points (n_points, dim) float32, true cluster (n_points,) int32)
    from ``seed``, an int or a sequence of ints."""
    c = centres(data)
    n, d = data["n_points"], data["dim"]
    rng = np.random.default_rng(seed)
    which = rng.integers(0, len(c), n).astype(np.int32)
    x = np.empty((n, d), np.float32)
    for r0 in range(0, n, BLOCK_ROWS):
        r1 = min(n, r0 + BLOCK_ROWS)
        x[r0:r1] = rng.standard_normal((r1 - r0, d), dtype=np.float32)
        x[r0:r1] *= np.float32(data["sigma"])
        x[r0:r1] += c[which[r0:r1]]
    return x, which


def rows(data: dict, seed) -> np.ndarray:
    """The configuration's points (``data`` is its ``data`` group)."""
    return draw(data, seed)[0]
