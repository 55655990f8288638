"""Self time of ``repro.level.join`` as a % of the traced window: candidate
generation (apriori_join)."""

from bench.metrics import spans


def read(ctx):
    return spans.share(ctx, __file__, "repro.level.join")
