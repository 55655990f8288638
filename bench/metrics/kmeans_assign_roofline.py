"""Share of the roofline of the k-means assignment kernel over the traced
window: the work of the window's completed clustering jobs (every job's
``iters + 1`` assignment passes over all points, from the configuration
and the request's parameters; ``bench.metrics.kmeans_work``) over the
summed device time of the kernel's events.  The kernel runs inside
``jit``, where no call can be recorded, so the work comes from the
shapes and not from a kernel recorder."""

from bench.metrics import kmeans_work, work

EVENTS = r"kmeans_assign_pallas"


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    n_events, secs = ctx.trace.kernel(EVENTS)
    jobs = [r for r in ctx.requests if r["ok"] and "k_local" in r["params"]]
    if not jobs or n_events == 0 or secs <= 0:
        return None
    data = ctx.cell.config["data"]
    ops = nbytes = 0.0
    for r in jobs:
        sites = r["params"].get("n_sites") or ctx.cell.config["service"]["n_sites"]
        n = data["n_points"] // sites * sites
        o, b = kmeans_work.kmeans_job(n, r["params"]["k_local"], data["dim"],
                                      r["params"]["iters"])
        ops, nbytes = ops + o, nbytes + b
    out = work.roofline(ops, nbytes, secs, ctx.peaks["bf16_flops"], ctx.peaks["hbm_bytes_per_s"])
    out.update(jobs=len(jobs), events=n_events)
    return out
