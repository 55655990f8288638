"""Self time of ``repro.engine`` as a % of the traced window: the workflow
engine's own scheduling, outside the jobs it runs (``repro.job``)."""

from bench.metrics import spans


def read(ctx):
    return spans.share(ctx, __file__, "repro.engine")
