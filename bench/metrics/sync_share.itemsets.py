"""Self time of ``repro.sync`` as a % of the traced window: the grid
miners' synchronisation jobs (gfm pool and decide, fdm announce, decide
and collect, cd reduce and collect)."""

from bench.metrics import spans


def read(ctx):
    return spans.share(ctx, __file__, "repro.sync")
