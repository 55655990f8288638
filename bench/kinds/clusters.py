"""The ``clusters`` dataset kind: a set of points clustered by the paper's
variance-based distributed clustering (``vclustering``, Algorithm 1).

Numbers compared (``LIMITS``; PERF.md gives the readings they were set
from), each answer against ``bench.vcluster_reference`` on the same
split and seeds:

* ``label_mismatch``: points whose global cluster differs from the
  reference's, each of the answer's clusters matched to the reference
  cluster it overlaps most.  Exact: limit 0.
* ``n_global_gap``: the gap in the number of global clusters.  Limit 0.
* ``subcluster_size_gap``: the largest gap of one site's sub-cluster
  size (slot by slot) between the answer's ``site_stats`` and the
  reference, in % of the site's points.  Points nearly tied between two
  sub-clusters of one true cluster may fall either way, and Lloyd's
  steps carry such a difference on; a share of the site's points, where
  a count of points would not, holds one limit at the cell's size and at
  a test's.
* ``stats_error``: the largest relative gap of a merged global cluster's
  statistics: its centre over the points' extent, and its SSE.

The border perturbation is judged through ``label_mismatch`` alone: a
point it moves, or fails to move, lands in another global cluster.  On
well-separated clusters, as at the cell's shape, it moves no point, and
then nothing it does is seen; the comparison logs how many points the
reference moved.

The control ``bf16`` is the reference itself with its points held in
bfloat16 (the distances of its seeding and Lloyd's steps, and its
sub-cluster statistics).  The faults: ``answer_altered`` (the border
perturbation moves one point into another cluster), ``half_batch`` (each
site's k-means sees the first half of its points twice) and
``perturbation_skipped`` (each point keeps the global cluster the merge
gave it; seen only where the reference moves points).  Each replaces a
jitted fan-out function at the module attribute the site jobs call, so
no jit cache can hide it.  ``KERNELS`` is empty: ``ops.kmeans_assign``
runs inside ``jit``, where no call can be recorded; the roofline reader
takes its work from the configuration.
"""

from __future__ import annotations

import sys

import numpy as np

from bench import vcluster_reference
from bench.checks import Check, patch

APP = "vclustering"

LIMITS = {
    "label_mismatch": 0,
    "n_global_gap": 0,
    "subcluster_size_gap": 1.5,
    "stats_error": 2e-3,
}

CONTROLS = ("bf16",)

KERNELS: dict = {}


def load(svc, dataset: str, data: dict, rows: np.ndarray) -> None:
    svc.register_dataset(dataset, "points", dim=data["dim"])
    svc.append_points(dataset, rows)


def host_answer(app: str, res) -> dict:
    if app != APP:
        raise ValueError(f"no comparison for app {app!r}")
    st = res.merged.stats
    sizes = np.asarray(st.sizes)
    roots = np.flatnonzero(sizes > 0)
    return {"labels": np.asarray(res.labels), "sizes": np.asarray(res.site_stats.sizes),
            "roots": roots, "g_sizes": sizes[roots],
            "g_centres": np.asarray(st.centers)[roots], "g_sse": np.asarray(st.sse)[roots]}


def _as_answer(c: vcluster_reference.Clustering) -> dict:
    return {"labels": c.labels, "sizes": c.sizes, "roots": c.roots, "g_sizes": c.g_sizes,
            "g_centres": c.g_centres, "g_sse": c.g_sse}


def _overlap(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """A table: each cluster of ``got`` -> the cluster of ``want`` it
    shares most points with (-1 for a label ``got`` never uses).  Labels
    are slot numbers, small non-negative integers."""
    width = int(max(got.max(), want.max())) + 1
    codes, counts = np.unique(got.astype(np.int64).ravel() * width + want.ravel(),
                              return_counts=True)
    g, w = np.divmod(codes, width)
    table = np.full(width, -1, np.int64)
    order = np.lexsort((counts, g))  # within each g, the largest overlap last
    table[g[order]] = w[order]
    return table


def checks_of(got: dict, ref: vcluster_reference.Clustering, extent: float) -> dict:
    """The four numbers of one answer against the reference's clustering."""
    match = _overlap(got["labels"], ref.labels)
    mapped = match[got["labels"]]
    where = {int(r): i for i, r in enumerate(ref.roots)}
    err = 0.0
    for a, centre, sse in zip(got["roots"], got["g_centres"], got["g_sse"]):
        i = where.get(int(match[a])) if a < len(match) else None
        if i is None:  # a cluster that kept none of its points
            err = np.inf
            break
        err = max(err, float(np.abs(centre - ref.g_centres[i]).max()) / extent,
                  abs(float(sse) - ref.g_sse[i]) / max(ref.g_sse[i], 1e-30))
    return {
        "label_mismatch": int((mapped != ref.labels).sum()),
        "n_global_gap": abs(len(got["roots"]) - ref.n_global),
        "subcluster_size_gap": 100.0 * float(np.abs(got["sizes"] - ref.sizes).max())
        / got["labels"].shape[1],
        "stats_error": err,
    }


def compare(rows: np.ndarray, answers: list, control: str | None) -> list[Check]:
    """Each answer against the reference run on the same split and seeds."""
    worst = dict.fromkeys(LIMITS, 0)
    extent = float(rows.max() - rows.min())
    for a in answers:
        p = a.params
        job = {"n_sites": a.value["labels"].shape[0], "k_local": p["k_local"],
               "iters": p["iters"], "seed": p["seed"], "split_seed": p["split_seed"]}
        ref = vcluster_reference.vcluster(rows, **job)
        print(f"[bench] reference: {ref.n_global} global clusters, {ref.n_merges} merges, "
              f"{ref.n_moved} points moved by the border perturbation", file=sys.stderr,
              flush=True)
        got = (_as_answer(vcluster_reference.vcluster(rows, **job, dtype="bfloat16"))
               if control == "bf16" else a.value)
        for name, v in checks_of(got, ref, extent).items():
            worst[name] = max(worst[name], v)
    return [Check(name, v, LIMITS[name]) for name, v in worst.items()]


# ---------------------------------------------------------------------------
# faults planted in the timed path (tests only)
# ---------------------------------------------------------------------------


def _one_point_moved(orig):
    def f(*a, **kw):
        import jax.numpy as jnp

        labels, counts = orig(*a, **kw)
        first = labels[0]
        other = first[jnp.argmax(first != first[0])]
        return labels.at[0, 0].set(other), counts
    return f


def _as_merged(orig):
    def f(xs, slots, merged, *a, **kw):
        import jax.numpy as jnp

        _, counts = orig(xs, slots, merged, *a, **kw)
        roots = merged.labels  # (M,), or (members, M) on the fused path
        if roots.ndim == 1:
            return roots[slots], counts
        return jnp.take_along_axis(roots, slots, axis=1), counts
    return f


def _first_half_twice(orig):
    def f(keys, xs, *a, **kw):
        import jax.numpy as jnp

        n = xs.shape[1]
        half = xs[:, : (n + 1) // 2]
        return orig(keys, jnp.concatenate([half, half], axis=1)[:, :n], *a, **kw)
    return f


def _answer_altered() -> list:
    from repro.core import vclustering

    return [patch(vclustering, "_perturb_batch", _one_point_moved),
            patch(vclustering, "_perturb_batch_many", _one_point_moved)]


def _half_batch() -> list:
    from repro.core import vclustering

    return [patch(vclustering, "_site_local_batch", _first_half_twice)]


def _perturbation_skipped() -> list:
    from repro.core import vclustering

    return [patch(vclustering, "_perturb_batch", _as_merged),
            patch(vclustering, "_perturb_batch_many", _as_merged)]


FAULTS = {"answer_altered": _answer_altered, "half_batch": _half_batch,
          "perturbation_skipped": _perturbation_skipped}
