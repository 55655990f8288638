"""Share of the roofline of the support-count kernel over the traced
window: the work of every call that ran (recorded at the
``repro.kernels.ops`` entry points with its sites, rows and non-empty
candidates) over the summed device time of the kernel's events."""

from bench.metrics import work

EVENTS = r"support_count"


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    n_events, secs = ctx.trace.kernel(EVENTS)
    if not ctx.kernel_calls or n_events == 0 or secs <= 0:
        return None
    n_items = ctx.cell.config["data"]["n_items"]
    ops = nbytes = 0.0
    for c in ctx.kernel_calls:
        o, b = work.support_count(c.n_tx * c.sites, c.n_cand_total / c.sites, n_items, c.words)
        ops, nbytes = ops + o, nbytes + b
    out = work.roofline(ops, nbytes, secs, ctx.peaks["int8_ops"], ctx.peaks["hbm_bytes_per_s"])
    out.update(calls=len(ctx.kernel_calls), events=n_events)
    return out
