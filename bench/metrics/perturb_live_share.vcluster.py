"""Share of the border perturbation's scan steps that carried a live
candidate, in %: the ``live`` over the ``steps`` stats of the window's
``repro.vc.perturb`` spans (each site scans M * b candidate slots, M the
sub-cluster slots of all sites, of which only the global clusters' b
border points are live).  Both counts, and the points the scan moved
(``moved``), are the program's own, read from the scan's outputs."""

from bench.metrics import span_stats


def read(ctx):
    got = span_stats.totals(ctx, __file__, "repro.vc.perturb")
    if got is None:
        return None
    _, sums = got
    if sums["steps"] <= 0:
        return None
    return {"value": 100.0 * sums["live"] / sums["steps"], "live": sums["live"],
            "steps": sums["steps"], "moved": sums["moved"]}
